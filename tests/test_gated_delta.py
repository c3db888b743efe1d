"""The chunked gated delta rule against its per-token recurrence, on both
of its paths: the ``lax.scan`` hand-off every CPU site takes, and the Pallas
chunk kernel, sent there by the test and interpreted."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from machine_learning_apache_spark_tpu import telemetry
from machine_learning_apache_spark_tpu.ops import gated_delta
from machine_learning_apache_spark_tpu.ops.gated_delta import (
    gated_delta_recurrent,
    gated_delta_rule,
)

PATHS = ("scan", "kernel")


@pytest.fixture
def take(monkeypatch):
    """``take("kernel")`` sends every site of the test through the Pallas
    kernel (interpreted: the backend is the CPU), whatever its shapes;
    ``take("scan")`` leaves the dispatch to what it observes here."""
    def choose(path):
        if path == "kernel":
            monkeypatch.setattr(
                gated_delta, "_kernel_refusal", lambda *a, **k: None
            )
    return choose


def _operands(seed, *, length, key_heads, value_heads, decay, b=2, dk=16, dv=8):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, length, key_heads, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, length, key_heads, dk)))
    v = jax.random.normal(ks[2], (b, length, value_heads, dv))
    g = -decay * jax.random.uniform(ks[3], (b, length, value_heads), minval=0.2)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, length, value_heads)))
    return q, k, v, g, beta


# decay 1e-3: exp(g) near 1 (the state is kept); 8.0: near 0 (forgotten at once)
@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("decay,length,chunk,key_heads,value_heads", [
    (1e-3, 50, 16, 2, 4), (0.1, 50, 16, 3, 3), (8.0, 50, 16, 2, 4),
    (1e-3, 100, 64, 2, 4), (0.1, 64, 64, 2, 4), (8.0, 100, 64, 2, 4),
    (0.1, 33, 64, 2, 4), (1e-3, 130, 64, 1, 2),
])
def test_chunked_matches_recurrence_values_and_gradients(
    take, path, decay, length, chunk, key_heads, value_heads
):
    take(path)
    args = _operands(
        length + chunk, length=length, key_heads=key_heads,
        value_heads=value_heads, decay=decay,
    )
    out, state = gated_delta_rule(*args, chunk=chunk)
    want, want_state = gated_delta_recurrent(*args)
    assert out.shape == want.shape == args[2].shape
    assert jnp.allclose(out, want, atol=1e-4, rtol=1e-4)
    assert jnp.allclose(state, want_state, atol=1e-4, rtol=1e-4)

    def scalar(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a)[0])) + jnp.sum(fn(*a)[1] ** 2)

    got = jax.grad(
        scalar(lambda *a: gated_delta_rule(*a, chunk=chunk)), argnums=range(5)
    )(*args)
    ref = jax.grad(scalar(gated_delta_recurrent), argnums=range(5))(*args)
    for name, a, b in zip("q k v g beta".split(), got, ref):
        assert jnp.allclose(a, b, atol=1e-4, rtol=1e-4), name


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("shift", [1.0, 4.0])
def test_keys_alike_as_a_positive_activation_leaves_them(take, path, shift):
    """Keys with a common component (mean cosine 0.5 and 0.9): the chunk's
    triangular system is ill-conditioned there, and forming its inverse as a
    product of powers loses every digit (read on the chip, PR 26)."""
    q, k, v, g, beta = _operands(
        11, length=128, key_heads=2, value_heads=4, decay=0.01, dk=32, dv=16
    )
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    k = unit(jax.nn.silu(k * 32 ** 0.5 + shift))
    cos = jnp.einsum("btd,bsd->bts", k[:, :, 0], k[:, :, 0])
    assert float(jnp.mean(cos)) > 0.45
    take(path)
    args = (q, k, v, g, beta)
    out, _ = gated_delta_rule(*args, chunk=64)
    want, _ = gated_delta_recurrent(*args)
    assert jnp.allclose(out, want, atol=1e-4, rtol=1e-4)
    loss = lambda fn: (lambda *a: jnp.sum(jnp.sin(fn(*a)[0])))  # noqa: E731
    got = jax.grad(loss(lambda *a: gated_delta_rule(*a, chunk=64)), argnums=range(5))(*args)
    ref = jax.grad(loss(gated_delta_recurrent), argnums=range(5))(*args)
    for a, b in zip(got, ref):
        assert jnp.allclose(a, b, atol=2e-4 * float(jnp.max(jnp.abs(b))) + 1e-6)


def _chunk_systems(chunk, shift, *, n=12, dk=128, dv=24):
    """``a`` and ``rhs`` of ``n`` chunks as ``gated_delta_rule`` forms them
    (float64 numpy), the keys built as the test above builds them: plain
    unit keys where ``shift`` is None, else a common component left by a
    positive activation. Also the keys' mean cosine."""
    rng = np.random.default_rng(chunk)
    k = rng.standard_normal((n, chunk, dk))
    if shift is not None:
        k = np.asarray(jax.nn.silu(k + shift), np.float64)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    gram = np.einsum("nid,njd->nij", k, k)
    beta = rng.uniform(0.05, 1.0, (n, chunk))
    big_g = np.cumsum(rng.uniform(-0.1, 0.0, (n, chunk)), axis=-1)
    decay = np.exp(np.minimum(big_g[:, :, None] - big_g[:, None, :], 0.0))
    a = np.tril(gram * decay * beta[:, :, None], -1)
    v = rng.standard_normal((n, chunk, dv))
    rhs = np.concatenate([v, k * np.exp(big_g)[..., None]], -1)
    return a, rhs * beta[..., None], float(np.mean(gram))


# shift None / 1.0 / 4.0: mean cosine between a chunk's keys 0.02 / 0.5 / 0.9
@pytest.mark.parametrize("shift,cosine", [(None, 0.0), (1.0, 0.45), (4.0, 0.85)])
@pytest.mark.parametrize("chunk", [8, 16, 32, 64, 48])
def test_blocked_inverse_is_as_exact_as_substitution(chunk, shift, cosine):
    """``_solve_unit_lower`` alone, float32, against a float64 solve: values
    within 1e-6 of the largest entry, both cotangents within 5e-6 of the
    float64 ones. (The squaring form over the whole chunk read 2e-3 at
    cosine 0.5 and 38 at 0.9; float32 substitution 2e-7.)"""
    a, rhs, cos = _chunk_systems(chunk, shift)
    assert cos >= cosine
    inverse = np.linalg.inv(np.eye(chunk) + a)
    want = inverse @ rhs
    weights = np.random.default_rng(1).standard_normal(want.shape)
    want_d_rhs = np.swapaxes(inverse, -1, -2) @ weights
    want_d_a = -np.tril(want_d_rhs @ np.swapaxes(want, -1, -2), -1)

    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    got = gated_delta._solve_unit_lower(f32(a), f32(rhs))
    d_a, d_rhs = jax.grad(
        lambda a, r: jnp.sum(gated_delta._solve_unit_lower(a, r) * f32(weights)),
        argnums=(0, 1),
    )(f32(a), f32(rhs))
    assert got.dtype == d_a.dtype == d_rhs.dtype == jnp.float32
    gap = lambda x, ref: np.max(np.abs(np.asarray(x, np.float64) - ref)) / np.max(np.abs(ref))  # noqa: E731
    assert gap(got, want) <= 1e-6
    assert gap(d_a, want_d_a) <= 5e-6
    assert gap(d_rhs, want_d_rhs) <= 5e-6


# 5 and 33: an odd side grows by a row and column of the identity; 24: two
# halves of 12; 40: 20, then 10
@pytest.mark.parametrize("chunk", [5, 24, 33, 40])
def test_inverse_vjp_agrees_with_autodiff_through_a_plain_solve(chunk):
    a, rhs, _ = _chunk_systems(chunk, 1.0, n=3, dk=16, dv=6)
    a, rhs = jnp.asarray(a, jnp.float32), jnp.asarray(rhs, jnp.float32)

    def plain(a, rhs):
        return jnp.linalg.solve(jnp.eye(chunk) + jnp.tril(a, -1), rhs)

    got, pull = jax.vjp(gated_delta._solve_unit_lower, a, rhs)
    want, want_pull = jax.vjp(plain, a, rhs)
    assert jnp.allclose(got, want, atol=2e-6, rtol=2e-6)
    cotangent = jax.random.normal(jax.random.key(chunk), want.shape)
    for name, x, ref in zip(("d_a", "d_rhs"), pull(cotangent), want_pull(cotangent)):
        assert x.shape == ref.shape, name
        assert jnp.allclose(x, ref, atol=1e-5, rtol=1e-5), name


@pytest.mark.parametrize("path", PATHS)
def test_initial_state_continues_a_sequence(take, path):
    take(path)
    args = _operands(7, length=48, key_heads=2, value_heads=4, decay=0.05)
    whole, final = gated_delta_rule(*args, chunk=16)
    head = [a[:, :20] for a in args]
    tail = [a[:, 20:] for a in args]
    first, state = gated_delta_rule(*head, chunk=16)
    second, final2 = gated_delta_rule(*tail, chunk=16, initial_state=state)
    assert jnp.allclose(jnp.concatenate([first, second], 1), whole, atol=1e-5)
    assert jnp.allclose(final2, final, atol=1e-5)


@pytest.mark.parametrize("path", PATHS)
def test_bfloat16_operands_stay_near_the_float32_recurrence(take, path):
    # bfloat16 carries 8 bits of mantissa: products of rounded operands with
    # float32 accumulation and a float32 state land within a few 2^-8.
    take(path)
    args = _operands(3, length=96, key_heads=2, value_heads=4, decay=0.05)
    cast = [a.astype(jnp.bfloat16) for a in args[:3]] + list(args[3:])
    out, _ = gated_delta_rule(*cast, chunk=32)
    want, _ = gated_delta_recurrent(*cast)
    assert out.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - want))) < 0.05


def test_value_heads_must_be_a_multiple_of_key_heads():
    args = _operands(1, length=8, key_heads=2, value_heads=3, decay=0.1)
    with pytest.raises(ValueError, match="multiple"):
        gated_delta_rule(*args, chunk=8)


def _with_state(fn):
    """``loss(q, k, v, g, beta, initial_state)`` over the outputs and the
    final state, so the initial state's cotangent and the final state's
    both flow."""
    def loss(*a):
        out, final = fn(*a[:5], initial_state=a[5])
        return jnp.sum(jnp.sin(out.astype(jnp.float32))) + jnp.sum(final ** 2)
    return loss


# key heads : value heads 1:1 and 1:2; lengths that are and are not multiples
# of the chunk (the tail is padded with positions that write nothing)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("length,chunk,key_heads,value_heads", [
    (64, 16, 4, 4), (70, 16, 2, 4), (45, 32, 3, 3), (96, 32, 1, 2),
])
def test_kernel_agrees_with_the_scan_path_and_the_recurrence(
    take, dtype, length, chunk, key_heads, value_heads
):
    """Values, the five gradients and the initial state's cotangent, from a
    non-zero initial state: the kernel against the ``lax.scan`` path (the
    same products and rounding points: float32 to the last bits, bfloat16 to
    its rounding) and against the recurrence."""
    args = _operands(
        length, length=length, key_heads=key_heads, value_heads=value_heads,
        decay=0.05,
    )
    args = [a.astype(dtype) for a in args[:3]] + list(args[3:])
    state = 0.3 * jax.random.normal(
        jax.random.key(length), (2, value_heads, 16, 8), jnp.float32
    )
    every = tuple(range(6))

    def read(fn):
        out, final = fn(*args, initial_state=state)
        grads = jax.grad(_with_state(fn), argnums=every)(*args, state)
        return [out, final, *grads]

    chunked = functools.partial(gated_delta_rule, chunk=chunk)
    scan = read(chunked)
    take("kernel")
    kernel = read(chunked)
    want = read(gated_delta_recurrent)
    names = "out final dq dk dv dg dbeta dstate".split()
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    # bfloat16: 2^-8 a rounding, a few roundings a chunk, on the reference's
    # own scale; float32 as the tests above
    near = 1e-4 if dtype == "float32" else 0.03
    same = 1e-5 if dtype == "float32" else 0.03
    for name, a, b, r in zip(names, kernel, scan, want):
        scale = 1.0 + float(jnp.max(jnp.abs(r)))
        assert a.dtype == b.dtype and a.shape == b.shape == r.shape, name
        assert float(jnp.max(jnp.abs(f32(a) - f32(b)))) <= same * scale, name
        assert float(jnp.max(jnp.abs(f32(a) - r))) <= near * scale, name


def test_the_primal_and_the_saving_forward_give_the_same_values(take):
    """Under ``jax.checkpoint`` the first pass runs the primal kernel (outputs
    and final state only), the backward's replay the saving one."""
    take("kernel")
    args = _operands(2, length=40, key_heads=2, value_heads=2, decay=0.1)
    fn = lambda *a: gated_delta_rule(*a, chunk=16)  # noqa: E731
    out, final = fn(*args)
    (out2, final2), _ = jax.vjp(fn, *args)
    assert jnp.array_equal(out, out2) and jnp.array_equal(final, final2)
    loss = lambda f: (lambda *a: jnp.sum(jnp.sin(f(*a)[0])))  # noqa: E731
    plain = jax.grad(loss(fn), argnums=range(5))(*args)
    remat = jax.grad(loss(jax.checkpoint(fn)), argnums=range(5))(*args)
    for a, b in zip(plain, remat):
        assert jnp.array_equal(a, b)


def _dispatches():
    return [
        e.attrs for e in telemetry.get_log().snapshot()
        if e.kind == "annotation" and e.name == "ops.gated_delta_dispatch"
    ]


def test_dispatch_record_names_the_path_and_why(take):
    args = _operands(4, length=32, key_heads=2, value_heads=4, decay=0.1)
    telemetry.get_log().clear()
    gated_delta_rule(*args, chunk=16, site="here")
    (seen,) = _dispatches()
    assert seen["site"] == "here" and seen["impl"] == "chunked_scan"
    assert "lax.scan over 2 chunks of 16" in seen["reason"]
    assert seen["reason"].endswith("backend cpu")
    assert seen["inverse"] == (
        "blocked 16x16 substitution, f32 HIGHEST, xla; applied and "
        "differentiated by f32 HIGHEST products"
    )

    take("kernel")
    telemetry.get_log().clear()
    gated_delta_rule(*args, chunk=16, site="here")
    (seen,) = _dispatches()
    assert seen["impl"] == "pallas_chunk"
    # 2 x 4 heads: all eight in one grid step, two chunks
    assert "gdn_chunk_fwd / gdn_chunk_bwd head_block 8 grid 1x2" in seen["reason"]
    assert "float32 at Precision.HIGHEST" in seen["reason"]
    assert seen["head_block"] == 8 and tuple(seen["grid"]) == (1, 2)
    assert set(seen["vmem_bytes"]) == {"fwd", "fwd_save", "bwd"}
    assert "f32 on the VPU, pallas gdn_inverse" in seen["inverse"]


@pytest.mark.parametrize("chunk,place,form", [
    (64, "xla", "blocked 16x16 substitution, merges 16>32>64, f32 HIGHEST, xla"),
    (48, "xla", "blocked 12x12 substitution, merges 12>24>48, f32 HIGHEST, xla"),
    (64, "pallas", "blocked 16x16 substitution, merges 16>32>64, f32 on the VPU"),
    (48, "pallas", "blocked 16x16 substitution, merges 16>32>64, f32 on the VPU"),
    (8, "pallas", "blocked 8x8 substitution, f32 on the VPU"),
])
def test_inverse_record_names_the_split_and_the_place(chunk, place, form):
    assert gated_delta._inverse_form(chunk, place).startswith(form)


@pytest.mark.parametrize("chunk,refusal,place", [
    (64, None, "pallas"), (48, None, "pallas"), (16, None, "pallas"),
    (128, None, "xla"), (64, "backend cpu", "xla"),
])
def test_inverse_is_built_in_the_kernel_where_the_site_takes_the_kernels(
    chunk, refusal, place
):
    """``gdn_inverse`` holds sides up to 64 in VMEM; a longer chunk at a
    kernel site builds the same form as plain JAX."""
    assert gated_delta._inverse_place(refusal, chunk) == place


@pytest.mark.parametrize("chunk", [8, 16, 32, 64, 48])
def test_inverse_kernel_agrees_with_the_plain_form(chunk):
    """``gdn_inverse`` (interpreted) against ``_inverse_unit_lower`` and a
    float64 inverse, keys at mean cosine 0.5, more systems than a lane tile
    and not a multiple of it."""
    from machine_learning_apache_spark_tpu.ops.pallas_gated_delta import (
        unit_lower_inverse,
    )

    a, _, _ = _chunk_systems(chunk, 1.0, n=130, dk=32)
    want = np.linalg.inv(np.eye(chunk) + a)
    a = jnp.asarray(a, jnp.float32).reshape(2, 65, chunk, chunk)
    got = unit_lower_inverse(
        a, side=gated_delta._kernel_side(chunk),
        block=gated_delta.SUBSTITUTION_BLOCK, interpret=True,
    )
    plain = gated_delta._inverse_unit_lower(a)
    assert got.shape == plain.shape == a.shape
    assert float(jnp.max(jnp.abs(got - plain))) <= 1e-6
    assert np.max(np.abs(np.asarray(got, np.float64).reshape(want.shape) - want)) <= 1e-6


@pytest.mark.parametrize("dtype,chunk,dk,dv,mesh_size,why", [
    ("bfloat16", 64, 128, 128, None, None),
    ("float32", 8, 256, 128, 1, None),
    ("bfloat16", 64, 96, 128, None, "dk 96, dv 128 not multiples of 128"),
    ("bfloat16", 64, 128, 64, None, "dk 128, dv 64 not multiples of 128"),
    ("bfloat16", 8, 128, 128, None, "bfloat16's 16 sublanes"),
    ("bfloat16", 64, 128, 128, 4, "a mesh of 4 devices is active"),
])
def test_a_tpu_site_takes_the_kernel_where_its_shapes_allow(
    monkeypatch, dtype, chunk, dk, dv, mesh_size, why
):
    from machine_learning_apache_spark_tpu.ops.attention import kernel_mesh
    from machine_learning_apache_spark_tpu.parallel.mesh import (
        data_parallel_mesh,
    )

    assert gated_delta._kernel_refusal(
        jnp.dtype(dtype), chunk, dk, dv
    ) == "backend cpu"
    monkeypatch.setattr(gated_delta, "_backend", lambda: "tpu")
    with kernel_mesh(mesh_size and data_parallel_mesh(mesh_size)):
        refusal = gated_delta._kernel_refusal(jnp.dtype(dtype), chunk, dk, dv)
    assert (refusal is None) if why is None else (why in refusal)


@pytest.mark.parametrize("heads,want", [
    (128, 16), (96, 16), (12, 12), (7, 7), (17, 1), (34, 2), (1, 1),
])
def test_head_block_divides_the_heads(heads, want):
    """The chooser never pads a head: a grid step takes a divisor of B*H."""
    got = gated_delta._choose_head_block(heads, 64, 128, 128, 2)
    assert got == want and heads % got == 0
    assert gated_delta._vmem_bytes(
        "bwd", got, 64, 128, 128, 2
    ) <= gated_delta.VMEM_BUDGET


def test_head_block_stays_under_the_vmem_budget():
    # float32 at dk 512: a head's blocks are eight times the cell's
    got = gated_delta._choose_head_block(128, 64, 512, 256, 4)
    assert 1 <= got < 16
    assert gated_delta._vmem_bytes(
        "bwd", got, 64, 512, 256, 4
    ) <= gated_delta.VMEM_BUDGET
