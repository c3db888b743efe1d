"""Gated delta rule — the linear-attention recurrence of Gated DeltaNet
(Yang, Kautz & Hatamizadeh, arXiv:2412.06464), in chunks.

A value head keeps a state ``S [dk, dv]``. At position ``t``, with key
``k_t``, query ``q_t`` (both already L2-normalised, the query scaled), value
``v_t``, log-decay ``g_t <= 0`` and write strength ``beta_t`` in (0, 1):

    S   <- exp(g_t) S
    u_t  = beta_t (v_t - S^T k_t)
    S   <- S + k_t u_t^T
    o_t  = S^T q_t

``gated_delta_recurrent`` is that loop, one position a ``lax.scan`` step: the
oracle of the tests and the bring-up smoke, and what a decode step would run.

``gated_delta_rule`` is the training path. Positions are taken ``chunk`` at a
time. Inside a chunk, with ``G_i`` the running sum of ``g`` and
``A[i, j] = beta_i (k_i . k_j) exp(G_i - G_j)`` for ``j < i``, the writes
``u`` solve the unit lower-triangular system ``(I + A) u = beta (v - exp(G)
k S0)`` (the WY representation of the product of the chunk's Householder-like
factors). With ``T = (I + A)^-1`` built once, in float32, and applied by a
product,

    u_i   = T (beta v) - T (beta exp(G) k) S0        # ``value`` - ``w`` S0
    o_i   = exp(G_i) q_i S0 + tril(q k^T exp(G_i - G_j)) u
    S_end = exp(G_C) S0 + (exp(G_C - G_i) k_i)^T u

The in-chunk preparation (``A``, ``T`` and its product giving ``value`` and
``w``, the decays, ``tril(q k^T decay)``) is batched over all chunks, and JAX
differentiates it.

``T`` is a blocked product form (``_solve_unit_lower``; until PR 32 XLA's
triangular solve, whose expansion inverted each chunk's 64 x 64 block by a
custom call of 10.9 ms a layer a pass at the benchmark's site): diagonal
blocks of at most 16 x 16 by forward substitution, merged by halves,
``T21 = -T22 (A21 T11)``, 16 -> 32 -> 64, the split read off ``chunk``; every
product of the build, of the application ``T [beta v | beta e^G k]`` and of
the VJP (``d rhs = T^T d solved``, ``d A = -tril(d rhs solved^T, -1)``: no
transposed solve, no second inversion) is a float32 product at
``Precision.HIGHEST``. It is as exact as substitution (1e-7 of the answer's
largest entry whatever the keys: ``_solve_unit_lower`` has the table),
because the entries of ``T`` and of its blocks stay at or under 1; the
squaring form ``(I - A)(I + A^2)(I + A^4)...`` is not, its powers of ``A``
reach 1e6 for keys as alike as a positive activation leaves them and cancel,
and the blocked form loses the same digits if a product's operands are
rounded to bfloat16. Where a site takes the kernels below the build runs in
one more (``gdn_inverse``: the chunks' index in the lanes, substitution and
merges as float32 multiply-adds on the VPU), anywhere else as plain JAX (the
same form; XLA lays the 16 x 16 blocks out in padded tiles and takes 21 ms
where the kernel takes about one); the ``inverse=`` of the dispatch record
says which.

What is sequential, the state's hand-off from chunk to
chunk and the two output products that read the state, runs in one of two
places, with the same products and the same two rounding points (the state
and ``u`` rounded to the operands' dtype before they enter a product):

* **``pallas_chunk``** — one Pallas (Mosaic) kernel a direction
  (``ops.pallas_gated_delta``: ``gdn_chunk_fwd``, ``gdn_chunk_bwd``), grid ``(B*H / head_block, chunks)``
  with the chunk axis sequential and the float32 state of ``head_block``
  heads in VMEM scratch across it; blocks are indexed ``(head, chunk)``
  straight out of the ``[B*H, n, C, d]`` arrays. The primal writes the
  outputs and the final state only; under differentiation the forward also
  writes each chunk's starting state (rounded) and ``u``, and the backward
  kernel walks the chunks in reverse with the state's cotangent in VMEM.
  Taken where the site can be seen to allow it (``_kernel_refusal``): a TPU
  backend, ``dk`` and ``dv`` multiples of 128, ``chunk`` a multiple of the
  dtype's sublane tile, no mesh of more than one device active.
* **``chunked_scan``** — anywhere else (every CPU test, odd head widths):
  one ``lax.scan`` over the chunks carries the float32 state, a second
  batched pass forms the outputs from each chunk's starting state, and the
  backward is JAX autodiff through the scan.

Which one a site took, and why, is its ``ops.gated_delta_dispatch`` record.
No per-token loop in either direction, either way.

Products take operands in the inputs' dtype and accumulate in float32;
decays, the inverse, its products and the state are float32 throughout. With float32 inputs every
product runs at ``Precision.HIGHEST`` (a float32 product on a TPU is
otherwise bfloat16 passes), which is what the tests and ``chip_smoke.py``
compare against the recurrence.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from machine_learning_apache_spark_tpu import telemetry

DEFAULT_CHUNK = 64


def record_dispatch(site: str, impl: str, reason: str, **shape) -> None:
    """Trace-time breadcrumb, as ``ops.attention.record_dispatch``: one
    ``ops.gated_delta_dispatch`` annotation per site per traced program."""
    telemetry.annotate(
        "ops.gated_delta_dispatch", site=site, impl=impl, reason=reason, **shape
    )


def _expand_heads(x: jnp.ndarray, heads: int) -> jnp.ndarray:
    """``[B, T, Hk, d] -> [B, T, heads, d]``: a key head serves
    ``heads // Hk`` consecutive value heads."""
    if x.shape[2] == heads:
        return x
    if heads % x.shape[2]:
        raise ValueError(
            f"{heads} value heads are not a multiple of {x.shape[2]} key heads"
        )
    return jnp.repeat(x, heads // x.shape[2], axis=2)


def gated_delta_recurrent(q, k, v, g, beta, *, initial_state=None):
    """The recurrence, one position a step, in float32 at full precision.

    ``q, k [B, T, Hk, dk]``, ``v [B, T, Hv, dv]``, ``g, beta [B, T, Hv]``.
    Returns ``(o [B, T, Hv, dv] float32, final state [B, Hv, dk, dv])``.
    """
    heads = v.shape[2]
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    q, k = f32(_expand_heads(q, heads)), f32(_expand_heads(k, heads))
    v, g, beta = f32(v), f32(g), f32(beta)
    hi = jax.lax.Precision.HIGHEST
    state = (
        jnp.zeros((v.shape[0], heads, q.shape[-1], v.shape[-1]), jnp.float32)
        if initial_state is None else f32(initial_state)
    )

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs  # [B, H, d] / [B, H]
        s = s * jnp.exp(g_t)[..., None, None]
        u = b_t[..., None] * (
            v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t, precision=hi)
        )
        s = s + k_t[..., :, None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=hi)

    time_major = lambda x: jnp.moveaxis(x, 1, 0)  # noqa: E731
    state, out = jax.lax.scan(
        step, state, tuple(map(time_major, (q, k, v, g, beta)))
    )
    return jnp.moveaxis(out, 0, 1), state


# Side of the diagonal blocks inverted by substitution; above it the inverse
# is merged from its halves by products.
SUBSTITUTION_BLOCK = 16
# The longest side ``gdn_inverse`` holds: its ``[128 C, C]`` blocks in and out
# and ``[C, C, 128]`` float32 scratch planes are 20.5 MiB of VMEM at 64.
INVERSE_MAX_SIDE = 64


def _dot_f32(spec: str, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """A float32 product at ``Precision.HIGHEST``, whatever the operands:
    the only kind the inverse, its application and its VJP may hold."""
    return jnp.einsum(
        spec, x, y, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _inverse_unit_lower(a: jnp.ndarray) -> jnp.ndarray:
    """``T = (I + a)^-1`` for strictly lower-triangular ``a [..., C, C]``,
    split by the shape: a side of at most ``SUBSTITUTION_BLOCK`` by forward
    substitution, a longer one at ``C // 2`` into

        [[T11, 0], [T21, T22]],   T21 = -T22 (A21 T11)

    with ``T11``, ``T22`` the halves' own inverses. No power of ``a`` is ever
    formed: every factor is a block of ``T`` itself, whose entries stay at
    or under 1 (each row is a product of the contractions
    ``I - beta k k^T``)."""
    size = a.shape[-1]
    if size <= SUBSTITUTION_BLOCK:
        # Row i of T is e_i - a[i, :i] T[:i, :], for all blocks at once, the
        # blocks' index minor. (XLA's TPU compiler lays the array out its own
        # way, the 16 x 16 pair in a tile padded to 128 lanes: 21 ms a build
        # at the benchmark's site, which is why a kernel site does not build
        # here.)
        lanes = jnp.moveaxis(a.reshape(-1, size, size), 0, -1)  # [C, C, N]
        eye = jnp.eye(size, dtype=a.dtype)[:, :, None]
        t = jnp.zeros_like(lanes)
        for i in range(size):
            t = t.at[i].set(
                eye[i] - jnp.sum(lanes[i, :i, None, :] * t[:i], axis=0)
            )
        return jnp.moveaxis(t, -1, 0).reshape(a.shape)
    if size % 2:  # an odd side: one more row and column of the identity
        grown = jnp.pad(a, [(0, 0)] * (a.ndim - 2) + [(0, 1), (0, 1)])
        return _inverse_unit_lower(grown)[..., :size, :size]
    half = size // 2
    # both halves in one call: every level is one batch of equal blocks
    t11, t22 = _inverse_unit_lower(
        jnp.stack([a[..., :half, :half], a[..., half:, half:]])
    )
    t21 = -_dot_f32(
        "...ij,...jk->...ik", t22,
        _dot_f32("...ij,...jk->...ik", a[..., half:, :half], t11),
    )
    return jnp.concatenate([
        jnp.concatenate([t11, jnp.zeros_like(t11)], -1),
        jnp.concatenate([t21, t22], -1),
    ], -2)


def _kernel_side(chunk: int) -> int | None:
    """The side ``gdn_inverse`` works at for a ``chunk``: half the
    substitution's block, doubled until it holds the chunk (halves then stay
    whole sublane tiles down to the block; the systems are grown by rows and
    columns of the identity); ``None`` over ``INVERSE_MAX_SIDE``."""
    side = SUBSTITUTION_BLOCK // 2
    while side < chunk:
        side *= 2
    return side if side <= INVERSE_MAX_SIDE else None


def _inverse_place(refusal: str | None, chunk: int) -> str:
    """Where a site builds the inverse: ``"pallas"`` (``gdn_inverse``) where
    it takes the chunk kernels (``refusal`` is ``None``) and the kernel holds
    a side of ``chunk`` in VMEM, else ``"xla"``."""
    return "xla" if refusal or _kernel_side(chunk) is None else "pallas"


def _inverse_form(chunk: int, place: str) -> str:
    """The dispatch record's ``inverse=``: the sides substituted and merged
    at this ``chunk``, the products' precision and the place."""
    sides = [_kernel_side(chunk) if place == "pallas" else chunk]
    while sides[0] > SUBSTITUTION_BLOCK:
        sides.insert(0, (sides[0] + 1) // 2)
    return (
        f"blocked {sides[0]}x{sides[0]} substitution"
        + (", merges " + ">".join(map(str, sides)) if sides[1:] else "")
        + (", f32 on the VPU, pallas gdn_inverse" if place == "pallas"
           else ", f32 HIGHEST, xla")
        + "; applied and differentiated by f32 HIGHEST products"
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _solve_unit_lower(
    a: jnp.ndarray, rhs: jnp.ndarray, place: str = "xla"
) -> jnp.ndarray:
    """``(I + a)^-1 rhs`` for strictly lower-triangular ``a [..., C, C]``, in
    float32: the inverse built once, blocked (``_inverse_unit_lower``, or
    the same form in ``gdn_inverse`` where ``place`` is ``"pallas"``), and
    applied by one product; the VJP is two more products of what the forward
    kept, with no transposed solve and no second inversion.

    As exact as the substitution (XLA's triangular solve) it replaced:
    largest error of the answer over its largest entry, float32 against a
    float64 solve, at mean key cosine 0.02 / 0.5 / 0.9: substitution
    1.9e-7 / 1.9e-7 / 1.8e-7, this form 6e-8 / 8e-8 / 1e-7 (the chip read
    9e-8 and 1.3e-7 at 0.52 and 0.93, cotangents 2e-7), where the squaring
    form ``(I - a)(I + a^2)(I + a^4)...`` reads 6e-8 / 2e-3 / 38: its powers
    of ``a`` reach 1e6 and cancel, while every factor here is a block of
    ``T``, whose entries stay at or under 1. That holds only while every
    product is a float32 one: with operands rounded to bfloat16 the blocked
    form reads 2e-3 / 3e-3 too."""
    return _solve_unit_lower_fwd(a, rhs, place)[0]


def _solve_unit_lower_fwd(a, rhs, place):
    if place == "pallas":
        # imported here: Pallas costs a second at import, and only a site
        # that takes the kernels needs it
        from machine_learning_apache_spark_tpu.ops.pallas_gated_delta import (
            unit_lower_inverse,
        )

        t = unit_lower_inverse(
            a, side=_kernel_side(a.shape[-1]), block=SUBSTITUTION_BLOCK,
            interpret=_backend() != "tpu",
        )
    else:
        t = _inverse_unit_lower(a)
    solved = _dot_f32("...ij,...jd->...id", t, rhs)
    return solved, (t, solved)


def _solve_unit_lower_bwd(place, kept, d_solved):
    # solved = T rhs and dT = -T da T, so d rhs = T^T d solved and
    # d a = -(d rhs) solved^T, kept where a has entries
    t, solved = kept
    d_rhs = _dot_f32("...ji,...jd->...id", t, d_solved)
    d_a = -jnp.tril(_dot_f32("...id,...jd->...ij", d_rhs, solved), -1)
    return d_a, d_rhs


_solve_unit_lower.defvjp(_solve_unit_lower_fwd, _solve_unit_lower_bwd)


# -- where the sequential part runs -----------------------------------------

_LANES = 128
# Heads a grid step. A step's products are small (a [64, 128] tile against a
# [128, 128] state) and a head's chain is sequential, so several heads a step
# give the scheduler independent chains and amortise the step's fixed cost:
# at the benchmark's site 16 heads a step read 2.7 times faster than 1 and
# 6 % faster than 8, and 32 do not fit Mosaic's default scoped VMEM
# (tools/gdn_chunk_sweep.py; PERF.md section 6, PR 29).
MAX_HEAD_BLOCK = 16
# What a launch may take of VMEM as ``_vmem_bytes`` reckons it (the compiled
# kernels allocate 0.8 to 0.9 of the reckoning): under Mosaic's default
# scoped limit of 16 MiB, so no launch has to ask for more.
VMEM_BUDGET = 12 * 2**20


def _backend() -> str:
    """The backend a launch is traced for: it decides both whether a site
    takes the kernel and, where a test sends one there on a CPU, that the
    kernel is interpreted."""
    return jax.default_backend()


def _sublanes(dtype) -> int:
    """Rows of the dtype's VMEM tile: 8 for float32, 16 for bfloat16."""
    return 32 // jnp.dtype(dtype).itemsize


def _kernel_refusal(dtype, chunk: int, dk: int, dv: int) -> str | None:
    """Why this site keeps the ``lax.scan`` hand-off; ``None`` where it can
    be seen to take the kernel."""
    from machine_learning_apache_spark_tpu.ops.attention import (
        active_kernel_mesh,
    )

    if _backend() != "tpu":
        return f"backend {_backend()}"
    if dk % _LANES or dv % _LANES:
        return f"dk {dk}, dv {dv} not multiples of {_LANES}"
    if chunk % _sublanes(dtype):
        return (
            f"chunk {chunk} not a multiple of {jnp.dtype(dtype).name}'s "
            f"{_sublanes(dtype)} sublanes"
        )
    mesh = active_kernel_mesh()
    if mesh is not None and mesh.size > 1:
        return f"a mesh of {mesh.size} devices is active"
    return None


def _vmem_bytes(
    kernel: str, head_block: int, chunk: int, dk: int, dv: int, itemsize: int
) -> int:
    """VMEM a grid step of ``kernel`` ("fwd", "fwd_save", "bwd") holds,
    reckoned: the pipeline's two buffers of every operand and output block
    (a block's last dimension padded to whole lanes, a ``[1, dv]`` float32
    row to 8 sublanes; the state enters and leaves as float32 blocks) and
    the float32 state in scratch."""
    def tile(rows, cols, size):
        return rows * -(-cols // _LANES) * _LANES * size

    wide_k, wide_v = tile(chunk, dk, itemsize), tile(chunk, dv, itemsize)
    square, row = tile(chunk, chunk, itemsize), tile(8, dv, 4)
    state, rounded = tile(dk, dv, 4), tile(dk, dv, itemsize)
    blocks = {
        "fwd": 3 * wide_k + 2 * wide_v + square + row,
        "fwd_save": 3 * wide_k + 3 * wide_v + square + row + rounded,
        "bwd": 6 * wide_k + 3 * wide_v + 2 * square + 2 * row + rounded,
    }[kernel]
    return head_block * (2 * (blocks + 2 * state) + state)


def _choose_head_block(
    heads: int, chunk: int, dk: int, dv: int, itemsize: int
) -> int:
    """Heads a grid step, off the launch's own shapes: the largest divisor
    of ``heads`` (``B*H``: no head is padded) up to ``MAX_HEAD_BLOCK`` whose
    backward kernel, the fullest of the three, is reckoned under
    ``VMEM_BUDGET``."""
    return max(
        hb for hb in range(1, min(heads, MAX_HEAD_BLOCK) + 1)
        if heads % hb == 0 and (
            hb == 1
            or _vmem_bytes("bwd", hb, chunk, dk, dv, itemsize) <= VMEM_BUDGET
        )
    )


def gated_delta_rule(
    q, k, v, g, beta, *, chunk: int = DEFAULT_CHUNK, initial_state=None,
    site: str = "gated_delta",
):
    """The chunked form (module docstring); same operands as
    ``gated_delta_recurrent``. Returns ``(o [B, T, Hv, dv] in v's dtype,
    final state [B, Hv, dk, dv] float32)``. ``T`` need not be a multiple of
    ``chunk``: the tail is padded with positions that write nothing."""
    b, t, heads, dv = v.shape
    dk = q.shape[-1]
    dtype = v.dtype
    precision = (
        jax.lax.Precision.HIGHEST if dtype == jnp.float32
        else jax.lax.Precision.DEFAULT
    )
    n = -(-t // chunk)
    pad = n * chunk - t

    def chunks(x):
        """``[B, T, H, ...] -> [B, H, n, chunk, ...]``."""
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape(b, n, chunk, *x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q = chunks(_expand_heads(q, heads).astype(dtype))
    k = chunks(_expand_heads(k, heads).astype(dtype))
    v = chunks(v)
    g = chunks(g.astype(jnp.float32))        # [B, H, n, C]
    beta = chunks(beta.astype(jnp.float32))  # [B, H, n, C]

    def dot(spec, x, y):
        return jnp.einsum(
            spec, x, y, precision=precision,
            preferred_element_type=jnp.float32,
        )

    big_g = jnp.cumsum(g, axis=-1)                      # G_i within a chunk
    # exp(G_i - G_j) for i >= j; the difference is <= 0 there, and masked
    # before the exponential elsewhere (G_i - G_j > 0 could overflow).
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    diff = big_g[..., :, None] - big_g[..., None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)

    kk = dot("bhnid,bhnjd->bhnij", k, k)
    a = jnp.where(strict, kk * decay * beta[..., :, None], 0.0)
    # One solve for both right-hand sides: T (beta v) and T (beta e^G k).
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    rhs = jnp.concatenate(
        [f32(v), f32(k) * jnp.exp(big_g)[..., None]], axis=-1
    ) * beta[..., None]
    refusal = _kernel_refusal(dtype, chunk, dk, dv)
    place = _inverse_place(refusal, chunk)
    solved = _solve_unit_lower(a, rhs, place).astype(dtype)
    value, w = solved[..., :dv], solved[..., dv:]
    g_end = big_g[..., -1]                                          # [B,H,n]
    k_tail = (k * jnp.exp(g_end[..., None] - big_g)[..., None]).astype(dtype)

    state0 = (
        jnp.zeros((b, heads, dk, dv), jnp.float32)
        if initial_state is None else initial_state.astype(jnp.float32)
    )

    q_decayed = (q * jnp.exp(big_g)[..., None]).astype(dtype)
    qk = (dot("bhnid,bhnjd->bhnij", q, k) * decay).astype(dtype)  # i >= j kept
    shape = dict(
        batch=b, length=t, heads=heads, dk=dk, dv=dv, dtype=str(dtype),
        inverse=_inverse_form(chunk, place),
    )

    if refusal is None:
        head_block = _choose_head_block(
            b * heads, chunk, dk, dv, dtype.itemsize
        )
        vmem = {
            kernel: _vmem_bytes(
                kernel, head_block, chunk, dk, dv, dtype.itemsize
            ) for kernel in ("fwd", "fwd_save", "bwd")
        }
        record_dispatch(
            site, "pallas_chunk",
            f"gdn_chunk_fwd / gdn_chunk_bwd head_block {head_block} grid "
            f"{b * heads // head_block}x{n} vmem "
            + " / ".join(f"{v / 2**20:.1f}" for v in vmem.values())
            + f" MiB (primal / saving forward / backward); of [{b * heads},"
            f"{n},{chunk},{dk}|{dv}] {dtype.name}"
            + (" at Precision.HIGHEST" if dtype == jnp.float32 else ""),
            head_block=head_block, grid=(b * heads // head_block, n),
            vmem_bytes=vmem, **shape,
        )
        flat = lambda x: x.reshape(b * heads, *x.shape[2:])  # noqa: E731
        # imported here: Pallas costs a second at import, and only a site
        # that takes the kernels needs it
        from machine_learning_apache_spark_tpu.ops.pallas_gated_delta import (
            chunk_scan,
        )

        out, final = chunk_scan(
            (head_block, _backend() != "tpu"), *map(flat, (
                w, value, k_tail, q_decayed, qk, jnp.exp(g_end), state0
            ))
        )
        out = out.reshape(b, heads, n, chunk, dv)
        final = final.reshape(b, heads, dk, dv)
    else:
        record_dispatch(
            site, "chunked_scan",
            f"lax.scan over {n} chunks of {chunk}, WY form inside a chunk: "
            + refusal, **shape,
        )

        def hand_off(s, xs):
            w_i, value_i, k_tail_i, g_end_i = xs
            u = value_i - dot("bhik,bhkv->bhiv", w_i, s.astype(dtype))
            u = u.astype(dtype)
            s_next = s * jnp.exp(g_end_i)[..., None, None] + dot(
                "bhik,bhiv->bhkv", k_tail_i, u
            )
            return s_next, (s.astype(dtype), u)

        chunk_major = lambda x: jnp.moveaxis(x, 2, 0)  # noqa: E731
        final, (starts, u) = jax.lax.scan(
            hand_off, state0,
            tuple(map(chunk_major, (w, value, k_tail, g_end))),
        )
        starts = jnp.moveaxis(starts, 0, 2)  # [B, H, n, dk, dv]
        u = jnp.moveaxis(u, 0, 2)            # [B, H, n, C, dv]
        out = dot("bhnid,bhndv->bhniv", q_decayed, starts) + dot(
            "bhnij,bhnjv->bhniv", qk, u
        )
    out = jnp.moveaxis(out, 1, 3).reshape(b, n * chunk, heads, dv)
    return out[:, :t].astype(dtype), final
