"""Pure-jnp oracle for flash-decode."""
import jax
import jax.numpy as jnp
import numpy as np


def decode_attention_ref(q, k_cache, v_cache, lengths, scale=None):
    b, h, dh = q.shape
    s, kv = k_cache.shape[1], k_cache.shape[2]
    qr = q.astype(jnp.float32).reshape(b, kv, h // kv, dh)
    logits = jnp.einsum("bkgd,bskd->bkgs", qr, k_cache.astype(jnp.float32))
    logits = logits / np.sqrt(dh) if scale is None else logits * scale
    valid = jnp.arange(s)[None, None, None, :] < lengths[:, None, None, None]
    logits = jnp.where(valid, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, h, v_cache.shape[-1]).astype(q.dtype)


def paged_decode_attention_ref(q, k_pool, v_pool, block_tables, lengths, scale=None, v_dim=None):
    """Oracle for the paged kernel: materialize each row's contiguous view
    by gathering its table's pool blocks, then run the dense oracle.
    Positions past ``lengths`` (including every trash-backed lane) are
    masked identically, so this also defines the paged<->contiguous
    equivalence the serving engine's bit-parity tests rely on.  With
    ``v_pool`` None the values are the first ``v_dim`` lanes of K."""
    b = q.shape[0]
    bs = k_pool.shape[1]
    s_pad = block_tables.shape[1] * bs
    k_view = k_pool[block_tables].reshape(b, s_pad, *k_pool.shape[2:])
    if v_pool is None:
        v_view = k_view[..., :v_dim]
    else:
        v_view = v_pool[block_tables].reshape(b, s_pad, *v_pool.shape[2:])
    return decode_attention_ref(q, k_view, v_view, lengths, scale)
