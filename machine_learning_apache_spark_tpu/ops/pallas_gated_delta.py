"""The sequential part of the chunked gated delta rule as Pallas TPU kernels:
the state's hand-off from chunk to chunk and the two output products that
read the state (``ops.gated_delta`` has the algorithm, the in-chunk
preparation, the gate that sends a site here and the choice of
``head_block``; it imports this module only where a site takes the kernels,
so a program that never does pays nothing for Pallas at import).

Per chunk and head, with ``s_hat`` the float32 state rounded to the operands'
dtype (products accumulate in float32):

    u   = value - w s_hat                   (rounded to the dtype)
    o   = qd s_hat + qk u
    s  <- decay s + k_tail^T u              (float32, stays in VMEM)

and in reverse, with ``ds`` the state's cotangent in VMEM:

    du      = qk^T do + k_tail ds           dqk = do u^T
    dqd     = do s_hat^T                    dk_tail = u ds^T
    ddecay  = sum(ds * s_hat)               dvalue = du
    dw      = -du s_hat^T                   ds <- decay ds + qd^T do - w^T du

One ``pallas_call`` a direction (``gdn_chunk_fwd``, ``gdn_chunk_bwd``), grid
``(B*H / head_block, chunks)`` with the chunk axis sequential and the float32
state of ``head_block`` heads in VMEM scratch across it; blocks are indexed
``(head, chunk)`` straight out of the ``[B*H, n, C, d]`` arrays. The primal
writes the outputs and the final state only; under differentiation the
forward also writes each chunk's starting state (rounded) and ``u``, and the
backward kernel walks the chunks in reverse.

``unit_lower_inverse`` (``gdn_inverse``) is the in-chunk preparation's
inverse ``(I + a)^-1`` in the same place, the blocked form of
``ops.gated_delta._inverse_unit_lower`` with the systems' index in the
lanes: a grid step takes 128 systems where they lie (``[128 * C, C]``: row
``r`` of all of them is one strided read), turns each such ``[128, C]`` on the
XLU into a row of ``[C, C, 128]``, and every row update of the 16 x 16 substitutions
and every term of the merges ``T21 = -T22 (A21 T11)`` is one float32
multiply and add over all 128 systems on the VPU (exact float32 products:
no pass of the MXU, which would take six for float32 and fill a 64-wide
tile by a quarter); the transposed inverse goes back through the XLU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NN = (((1,), (0,)), ((), ()))  # x y
_NT = (((1,), (1,)), ((), ()))  # x y^T
_TN = (((0,), (0,)), ((), ()))  # x^T y


def _dot(x, y, dims, precision):
    """A kernel's product: operands as they are, float32 accumulation."""
    return jax.lax.dot_general(
        x, y, dims, precision=precision, preferred_element_type=jnp.float32
    )


def _chunk_fwd_kernel(
    w_ref, value_ref, k_tail_ref, qd_ref, qk_ref, decay_ref, s0_ref,
    o_ref, final_ref, *rest, heads: int, precision,
):
    # rest: the saving forward's (starts, u) outputs, then the state scratch
    *saved, s_scr = rest
    dtype = w_ref.dtype

    dot = functools.partial(_dot, precision=precision)

    @pl.when(pl.program_id(1) == 0)
    def _first_chunk():
        s_scr[...] = s0_ref[...]

    for h in range(heads):
        s = s_scr[h]
        s_hat = s.astype(dtype)
        u = (
            value_ref[h].astype(jnp.float32) - dot(w_ref[h], s_hat, _NN)
        ).astype(dtype)
        o_ref[h] = (
            dot(qd_ref[h], s_hat, _NN) + dot(qk_ref[h], u, _NN)
        ).astype(o_ref.dtype)
        s_scr[h] = s * decay_ref[h] + dot(k_tail_ref[h], u, _TN)
        if saved:
            starts_ref, u_ref = saved
            starts_ref[h] = s_hat
            u_ref[h] = u

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _last_chunk():
        final_ref[...] = s_scr[...]


def _chunk_bwd_kernel(
    w_ref, k_tail_ref, qd_ref, qk_ref, decay_ref, starts_ref, u_ref, do_ref,
    dfinal_ref,
    dw_ref, dvalue_ref, dk_tail_ref, dqd_ref, dqk_ref, ddecay_ref, ds0_ref,
    ds_scr, *, heads: int, precision,
):
    dtype = w_ref.dtype

    dot = functools.partial(_dot, precision=precision)

    @pl.when(pl.program_id(1) == 0)  # the last chunk: the grid runs reversed
    def _last_chunk():
        ds_scr[...] = dfinal_ref[...]

    for h in range(heads):
        ds = ds_scr[h]
        ds_hat = ds.astype(dtype)
        s_hat, u, do = starts_ref[h], u_ref[h], do_ref[h]
        du = (
            dot(qk_ref[h], do, _TN) + dot(k_tail_ref[h], ds_hat, _NN)
        ).astype(dtype)
        dvalue_ref[h] = du
        dqk_ref[h] = dot(do, u, _NT).astype(dtype)
        dqd_ref[h] = dot(do, s_hat, _NT).astype(dtype)
        dk_tail_ref[h] = dot(u, ds_hat, _NT).astype(dtype)
        dw_ref[h] = (-dot(du, s_hat, _NT)).astype(dtype)
        # summed over dk here, over dv by the launcher
        ddecay_ref[h] = jnp.sum(
            ds * s_hat.astype(jnp.float32), axis=0, keepdims=True
        )
        ds_scr[h] = (
            ds * decay_ref[h] + dot(qd_ref[h], do, _TN)
            - dot(w_ref[h], du, _TN)
        )

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _first_chunk():
        ds0_ref[...] = ds_scr[...]


def _launch(
    kernel: str, cfg, operands, outputs, reverse: bool = False
):
    """One ``pallas_call`` over ``[B*H, n, rows, cols]`` operands and
    per-head ``[B*H, dk, dv]`` float32 states: grid ``(B*H / head_block,
    n)``, the chunk axis sequential (walked from the last chunk where
    ``reverse``). ``cfg``: ``(head_block, interpret)``; ``outputs``:
    ``(shape, dtype)`` of each result."""
    head_block, interpret = cfg
    bh, n, chunk, dk = operands[0].shape
    dv = next(x.shape[-1] for x in operands if x.ndim == 3)
    dtype = operands[0].dtype

    def spec(shape):
        if len(shape) == 3:  # a state: one block a head block
            return pl.BlockSpec(
                (head_block, *shape[1:]), lambda i, c: (i, 0, 0)
            )
        at = (lambda i, c: (i, n - 1 - c, 0, 0)) if reverse else (
            lambda i, c: (i, c, 0, 0)
        )
        return pl.BlockSpec((head_block, None, *shape[2:]), at)

    return pl.pallas_call(
        functools.partial(
            _chunk_bwd_kernel if reverse else _chunk_fwd_kernel,
            heads=head_block,
            precision=(
                jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None
            ),
        ),
        grid=(bh // head_block, n),
        in_specs=[spec(x.shape) for x in operands],
        out_specs=[spec(shape) for shape, _ in outputs],
        out_shape=[jax.ShapeDtypeStruct(*o) for o in outputs],
        scratch_shapes=[pltpu.VMEM((head_block, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=kernel,
    )(*operands)


# -- the in-chunk inverse ------------------------------------------------------

_LANES = 128  # systems a grid step of ``gdn_inverse``: one to a lane


def _invert_in_lanes(a, t, x, lo: int, side: int, block: int) -> None:
    """``t[lo:lo+side, lo:lo+side] <- (I + a[lo:lo+side, lo:lo+side])^-1``,
    entry ``(i, j)`` of all 128 systems at ``ref[i, j, :]``; the upper
    triangle of ``a`` is not read. ``x [side/2, side/2, 128]`` is scratch."""
    f32 = jnp.float32

    def entry(ref, i, j):  # one entry of every system, as a [1, 128] row
        return ref[i, pl.ds(j, 1), :]

    if side <= block:
        column = jax.lax.broadcasted_iota(jnp.int32, (side, _LANES), 0)
        t[lo:lo + side, lo:lo + side, :] = jnp.zeros((side, side, _LANES), f32)

        def row(i, _):  # T[i, :] = e_i - a[i, :i] T[:i, :]; rows >= i are 0
            acc = (column == i).astype(f32)
            for j in range(side):
                acc = acc - jnp.where(
                    j < i, entry(a, lo + i, lo + j), 0.0
                ) * t[lo + j, lo:lo + side, :]
            t[lo + i, lo:lo + side, :] = acc

        jax.lax.fori_loop(0, side, row, None)
        return

    half = side // 2
    _invert_in_lanes(a, t, x, lo, half, block)
    _invert_in_lanes(a, t, x, lo + half, half, block)
    mid = lo + half

    def product(first, second):
        """``sum_k first(k) second(k)`` over the half, in four chains so
        that a term does not wait for the one before it."""
        chains = [
            sum(first(k) * second(k) for k in range(c, half, 4))
            for c in range(4)
        ]
        return (chains[0] + chains[1]) + (chains[2] + chains[3])

    def x_row(i, _):  # X = A21 T11
        x[i, :half, :] = product(
            lambda k: entry(a, mid + i, lo + k),
            lambda k: t[lo + k, lo:mid, :],
        )

    def t21_row(i, _):  # T21 = -T22 X, and zeros over it
        t[mid + i, lo:mid, :] = -product(
            lambda k: entry(t, mid + i, mid + k), lambda k: x[k, :half, :]
        )
        t[lo + i, mid:mid + half, :] = jnp.zeros((half, _LANES), f32)

    jax.lax.fori_loop(0, half, x_row, None)
    jax.lax.fori_loop(0, half, t21_row, None)


def _inverse_kernel(a_ref, t_ref, a_scr, t_scr, x_scr, *, side: int, block: int):
    # a_ref, t_ref [128 * C, C]: row r of system s at s * C + r. Row r of all
    # 128 systems is one strided read, and one turn of the XLU puts the
    # systems in the lanes; the inverse goes back the same way.
    for r in range(side):
        a_scr[r] = a_ref[pl.ds(r, _LANES, stride=side), :].T
    _invert_in_lanes(a_scr, t_scr, x_scr, 0, side, block)
    for r in range(side):
        t_ref[pl.ds(r, _LANES, stride=side), :] = t_scr[r].T


def _inverse_vmem_bytes(side: int) -> int:
    """VMEM a grid step of ``gdn_inverse`` holds: two buffers each of the
    ``[128 * C, C]`` block in and out (``C`` padded to whole lanes) and the
    ``[C, C, 128]`` scratch planes."""
    block = _LANES * side * -(-side // _LANES) * _LANES * 4
    return 4 * block + (2 * side * side + (side // 2) ** 2) * _LANES * 4


@functools.partial(jax.jit, static_argnames=("side", "block", "interpret"))
def unit_lower_inverse(a, *, side: int, block: int, interpret: bool):
    """``(I + a)^-1`` for strictly lower-triangular float32 ``a [..., C, C]``,
    worked at ``side >= C`` (``ops.gated_delta._kernel_side``: ``block`` or
    half of it, doubled until it holds ``C``, so that halves stay whole
    sublane tiles): diagonal blocks of ``block`` by substitution, merged by
    halves. The kernel reads and writes the systems where they lie (a
    ``[N * C, C]`` view; nothing is re-laid out around it where ``C`` is
    ``side`` and ``N`` a multiple of 128)."""
    chunk = a.shape[-1]
    count = a.size // (chunk * chunk)
    padded = -(-count // _LANES) * _LANES
    # systems of the identity fill the last lane tile; rows and columns of
    # it grow a chunk to the side
    flat = jnp.pad(
        a.reshape(count, chunk, chunk),
        ((0, padded - count), (0, side - chunk), (0, side - chunk)),
    ).reshape(padded * side, side)
    spec = pl.BlockSpec((_LANES * side, side), lambda i: (i, 0))
    plane = pltpu.VMEM((side, side, _LANES), jnp.float32)
    vmem = _inverse_vmem_bytes(side)
    inverse = pl.pallas_call(
        functools.partial(_inverse_kernel, side=side, block=block),
        grid=(padded // _LANES,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(flat.shape, jnp.float32),
        scratch_shapes=[
            plane, plane,
            pltpu.VMEM((side // 2, side // 2, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # Mosaic's default scoped limit is 16 MiB
            vmem_limit_bytes=vmem * 5 // 4 if vmem > 12 * 2**20 else None,
        ),
        interpret=interpret,
        name="gdn_inverse",
    )(flat)
    inverse = inverse.reshape(padded, side, side)[:count, :chunk, :chunk]
    return inverse.reshape(a.shape)


def _lanes(decay: jnp.ndarray, dv: int) -> jnp.ndarray:
    """``[B*H, n] -> [B*H, n, 1, dv]``: a chunk's scalar as a lane row, the
    shape a block of it can take."""
    return jnp.broadcast_to(decay[..., None, None], (*decay.shape, 1, dv))


# The launchers are jitted so that a kernel's HLO name is its own
# (``%gdn_chunk_fwd.N``) under whatever transformation traced the caller.
@functools.partial(jax.jit, static_argnames=("cfg", "save"))
def _forward(w, value, k_tail, qd, qk, decay, state0, *, cfg, save):
    bh, n, chunk, dk = w.shape
    dv, dtype = value.shape[-1], w.dtype
    outputs = [((bh, n, chunk, dv), dtype), ((bh, dk, dv), jnp.float32)]
    if save:  # each chunk's starting state, rounded, and its u
        outputs += [((bh, n, dk, dv), dtype), ((bh, n, chunk, dv), dtype)]
    return _launch(
        "gdn_chunk_fwd", cfg,
        (w, value, k_tail, qd, qk, _lanes(decay, dv), state0), outputs,
    )


@functools.partial(jax.jit, static_argnames=("cfg",))
def _backward(w, k_tail, qd, qk, decay, starts, u, do, dfinal, *, cfg):
    bh, n, chunk, dk = w.shape
    dv, dtype = u.shape[-1], w.dtype
    wide_k, wide_v = ((bh, n, chunk, dk), dtype), ((bh, n, chunk, dv), dtype)
    *grads, ddecay, ds0 = _launch(
        "gdn_chunk_bwd", cfg,
        (w, k_tail, qd, qk, _lanes(decay, dv), starts, u, do, dfinal),
        [wide_k, wide_v, wide_k, wide_k, ((bh, n, chunk, chunk), dtype),
         ((bh, n, 1, dv), jnp.float32), ((bh, dk, dv), jnp.float32)],
        reverse=True,
    )
    return *grads, jnp.sum(ddecay, axis=(-2, -1)), ds0


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def chunk_scan(cfg, w, value, k_tail, qd, qk, decay, state0):
    """The hand-off and the outputs over ``[B*H, n, ...]`` operands:
    ``(o [B*H, n, C, dv], final state [B*H, dk, dv] float32)``; ``cfg`` is
    ``(head_block, interpret)``."""
    return tuple(
        _forward(w, value, k_tail, qd, qk, decay, state0, cfg=cfg, save=False)
    )


def _chunk_scan_fwd(cfg, w, value, k_tail, qd, qk, decay, state0):
    o, final, starts, u = _forward(
        w, value, k_tail, qd, qk, decay, state0, cfg=cfg, save=True
    )
    return (o, final), (w, k_tail, qd, qk, decay, starts, u)


def _chunk_scan_bwd(cfg, residuals, cotangents):
    return tuple(_backward(*residuals, *cotangents, cfg=cfg))


chunk_scan.defvjp(_chunk_scan_fwd, _chunk_scan_bwd)
