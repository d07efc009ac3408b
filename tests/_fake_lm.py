"""Deterministic stand-in LM shared by the scheduler/streaming tests.

Next token is always ``(cur + 1) % VOCAB``, so the exact answer of every
request — including where EOS lands — is computable in closed form.
"""
import jax.numpy as jnp
import numpy as np

from repro.data.tokenizer import EOS
from repro.runtime.sharding import ShardingPolicy, base_rules

POL = ShardingPolicy(rules=base_rules(False), mesh=None)
VOCAB = 256


class FakeLM:
    """Deterministic LM: next token is (cur + 1) % vocab.  A prompt whose
    last token is e generates e+1, e+2, ... so EOS (=2) arrives exactly
    (2 - e - 1) % vocab + 1 tokens after prefill."""

    @staticmethod
    def _logits(tokens, offset=1):
        nxt = (tokens + offset) % VOCAB
        return jnp.eye(VOCAB, dtype=jnp.float32)[nxt]

    @staticmethod
    def _offset(params):
        # params rides the offset so a speculative DRAFTER can follow a
        # deliberately different rule than the target (offset=2 drafts
        # always diverge -> every draft rejected, outputs must not move)
        return params.get("offset", 1) if isinstance(params, dict) else 1

    @staticmethod
    def prefill(cfg, pol, params, batch, cache_len=None):
        tokens = batch["tokens"]
        logits = FakeLM._logits(tokens, FakeLM._offset(params))
        return logits, FakeLM.init_cache(cfg, tokens.shape[0], cache_len)

    @staticmethod
    def decode_step(cfg, pol, params, cache, tokens, pos, block_tables=None, block_size=0,
                    mesh=None, live=None, counters=False):
        return FakeLM._logits(tokens, FakeLM._offset(params)), cache

    @staticmethod
    def init_cache(cfg, batch, cache_len, dtype=jnp.float32, abstract=False):
        # same (n_blocks, B, ...) leaf layout contract as the real cache
        return {"dummy": jnp.zeros((1, batch, 1), jnp.float32)}

    @staticmethod
    def init_paged_cache(cfg, n_pool_blocks, block_size, n_slots, dtype=jnp.float32,
                         n_shards=None):
        # stateless model: the paged cache carries no information either,
        # but keeps the per-slot leaf contract so slot scatters typecheck
        return {"dummy": jnp.zeros((1, n_slots, 1), jnp.float32)}

    @staticmethod
    def paged_copy_block(cfg, cache, src, dst):
        return cache  # no pooled K/V to copy

    @staticmethod
    def mixed_step(cfg, pol, params, tokens, cache, block_tables, q_start, q_len,
                   block_size, mesh=None):
        # stateless next-token rule: per-lane logits are all the unified
        # engine reads (it takes lane q_len - 1), so no pool K/V needed
        return FakeLM._logits(tokens, FakeLM._offset(params)), cache

    @staticmethod
    def verify_step(cfg, pol, params, tokens, cache, block_tables, q_start, q_len,
                    block_size, mesh=None):
        # the stateless rule is position-free, so per-lane verify logits
        # ARE the plain-decode logits — same contract as LM.verify_step
        return FakeLM.mixed_step(
            cfg, pol, params, tokens, cache, block_tables, q_start, q_len, block_size
        )


def expected_answer(end_token: int, budget: int) -> list[int]:
    """Closed-form answer of the FakeLM for a prompt ending in end_token."""
    toks, x = [], end_token
    while len(toks) < budget:
        x = (x + 1) % VOCAB
        toks.append(x)
        if x == EOS:
            break
    return toks


def prompt_ending(end_token: int, length: int = 5) -> np.ndarray:
    p = np.full((length,), 7, np.int32)
    p[-1] = end_token
    return p


def make_fake_engine(monkeypatch, max_batch=2, max_new_tokens=6, sched_chunk=3, **scfg_kw):
    """ServeEngine over the FakeLM (monkeypatched in place of the real
    model module) with the qwen3 smoke config's 256-token vocab.
    ``scfg_kw`` passes through to ServeConfig (paged/block_size/...)."""
    import repro.serving.engine as engine_mod
    from repro.configs import get_config, smoke_config
    from repro.serving.engine import ServeConfig, ServeEngine

    monkeypatch.setattr(engine_mod, "LM", FakeLM)
    cfg = smoke_config(get_config("qwen3-0.6b")).with_overrides(dtype="float32")
    assert cfg.vocab_size == VOCAB
    return ServeEngine(
        cfg, POL, {},
        ServeConfig(
            max_batch=max_batch, max_prompt_len=8,
            max_new_tokens=max_new_tokens, sched_chunk=sched_chunk, **scfg_kw,
        ),
    )
