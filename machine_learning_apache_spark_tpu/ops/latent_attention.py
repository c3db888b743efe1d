"""Multi-head latent attention (DeepSeek-V2/V3) over a paged latent store, in
the absorbed form.

Every position leaves one row of ``kv_rank + rope_dim`` values a layer, ``[c_kv
| k_rope]``, shared by all heads: the normed key latent and the rotated
positional key. The up-projected form attends with ``k_h = [W_UK,h c_kv |
k_rope]`` and ``v_h = W_UV,h c_kv``; the absorbed form moves ``W_UK,h`` onto
the query and ``W_UV,h`` past the softmax, so that the store is read as it
lies:

    q~_h = W_UK,h^T q_nope,h                      (kv_rank values)
    score_h(s) = (q~_h . c_kv,s + q_rope,h . k_rope,s) * scale
    o~_h = sum_s softmax(score_h)(s) c_kv,s        o_h = W_UV,h o~_h

The plane is a matrix of rows, ``[pages * page, width]`` (``kv_rank +
rope_dim`` and any padding the caller keeps), so a position is written in
place (``write_rows``) and a selection is a gather of rows through the block
tables (``ops.dsa_index.select`` gives the rows). Plain XLA.
"""

from __future__ import annotations

import jax.numpy as jnp

from machine_learning_apache_spark_tpu import telemetry
from machine_learning_apache_spark_tpu.ops.sparse_block_attention import (
    masked_softmax,
)


def record_dispatch(site: str, impl: str, reason: str, **shape) -> None:
    telemetry.annotate(
        "ops.latent_attention_dispatch", site=site, impl=impl, reason=reason,
        **shape,
    )


def absorb_query(q_nope, w_uk):
    """``q_nope [N, H, dn]``, ``w_uk [kv_rank, H, dn]`` -> ``q~ [N, H,
    kv_rank]`` in ``q_nope``'s dtype (float32 accumulation)."""
    return jnp.einsum(
        "nhd,chd->nhc", q_nope, w_uk, preferred_element_type=jnp.float32
    ).astype(q_nope.dtype)


def expand_output(o_latent, w_uv, dtype):
    """``o~ [N, H, kv_rank]`` float32, ``w_uv [kv_rank, H, dv]`` -> ``[N, H,
    dv]`` float32."""
    return jnp.einsum(
        "nhc,chd->nhd", o_latent.astype(dtype), w_uv,
        preferred_element_type=jnp.float32,
    )


def write_rows(plane, rows, values):
    """``values [N, width]`` into the plane's ``rows [N]``, in place."""
    return plane.at[rows].set(values.astype(plane.dtype))


def attend_rows(q, latent, valid, *, kv_rank: int, scale: float):
    """``q [N, H, kv_rank + rope_dim]`` (``[q~ | q_rope]``) over the gathered
    rows ``latent [N, K, >= kv_rank + rope_dim]`` (a stored row may be
    padded past them) where ``valid [N, K]``: ``o~ [N, H, kv_rank]``
    float32."""
    s = jnp.einsum(
        "nhe,nke->nhk", q, latent[..., :q.shape[-1]],
        preferred_element_type=jnp.float32,
    ) * scale
    p = masked_softmax(s, valid[:, None, :], -1)
    return jnp.einsum(
        "nhk,nkc->nhc", p.astype(latent.dtype), latent[..., :kv_rank],
        preferred_element_type=jnp.float32,
    )


def attend_selected(q, plane, rows, valid, *, kv_rank: int, scale: float,
                    site: str):
    """Gather the selected rows (``rows [N, K]`` of the plane) and attend
    over them: ``o~ [N, H, kv_rank]`` float32."""
    record_dispatch(
        site, "xla_gather", "the one path", queries=q.shape[0],
        heads=q.shape[1], width=q.shape[2], rows_a_query=rows.shape[1],
    )
    latent = plane[jnp.where(valid, rows, 0)]
    return attend_rows(q, latent, valid, kv_rank=kv_rank, scale=scale)
