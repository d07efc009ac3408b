"""Jitted public wrapper: the Pallas kernel behind ``use_pallas``, the jnp
oracle otherwise (the kernel runs interpreted on the CPU, so CPU tests run
the same code through the interpreter)."""
import functools

import jax

from repro.kernels.retrieval_topk.kernel import retrieval_topk_pallas
from repro.kernels.retrieval_topk.ref import retrieval_topk_ref


@functools.partial(jax.jit, static_argnames=("k", "use_pallas"))
def retrieval_topk(queries, corpus, k: int, use_pallas: bool = False):
    """queries: (Q, D); corpus: (N, D) -> (scores (Q, k), idx (Q, k)).

    Batched natively over the query dimension: Q may be a single query or
    a whole request batch (B*Q rows) — one call, one kernel launch.
    """
    if use_pallas:
        return retrieval_topk_pallas(queries, corpus, k)
    return retrieval_topk_ref(queries, corpus, k)
