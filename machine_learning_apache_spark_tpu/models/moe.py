"""Mixture-of-experts feed-forward — expert parallelism for the zoo.

The reference has no MoE anywhere (SURVEY.md §2.3: EP "out of scope" for
parity); this module is capability headroom completing the mesh's five
axes (``parallel.mesh``: data/model/seq/pipeline/expert). Design follows
the canonical TPU MoE shape (Switch Transformer-style top-1 routing with
static capacity, one-hot einsum dispatch/combine — the Shazeer/Fedus
lineage all public TPU MoE code uses, e.g. mesh-tensorflow/flaxformer):

- **Static shapes**: every tensor has a compile-time shape. Each sequence
  is its own routing group with ``capacity = ceil(capacity_factor × seq /
  num_experts)`` slots per expert (the mesh-tf/flaxformer grouping — it
  bounds the dispatch tensor at ``cf·b·s²`` rather than ``cf·(b·s)²``);
  overflow tokens are *dropped* — their FFN output is zero and the
  surrounding residual connection carries them through unchanged (the
  standard Switch behavior, not a bug).
- **Einsum dispatch**: a boolean dispatch tensor ``D[b, s, e, c]`` gathers
  token features into per-expert buffers ``[E, B, C, d]``; the expert FFNs
  are one batched matmul pair over the leading expert dim; a weighted
  combine scatters results back. No gather/scatter ops, no dynamic shapes —
  XLA tiles everything onto the MXU.
- **Expert parallelism**: expert weights carry the logical axis ``"expert"``
  on their leading dim (→ mesh axis ``"expert"`` via
  ``parallel.tensor_parallel.DEFAULT_RULES``). Under ``pjit`` XLA partitions
  the dispatch einsum into an all-to-all-shaped exchange and each device
  runs only its experts — the scaling-book recipe, nothing hand-scheduled.
- **Load balancing**: the Switch auxiliary loss ``E · Σ_e f_e · p_e``
  (fraction-routed × mean-router-prob) is sown into the ``"losses"``
  collection; training code adds ``moe_aux_weight ×`` their mean to the task
  loss (see ``recipes.translation.make_translation_loss``).

Router numerics are float32 regardless of compute dtype (softmax over a
handful of logits is precision-critical; bf16 router probs destabilize
balancing).
"""

from __future__ import annotations

import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp


class MoEFeedForward(nn.Module):
    """Drop-in replacement for the dense position-wise FFN.

    Input/output ``[B, S, d_model]``; interface-compatible with
    ``transformer.FeedForward`` so encoder/decoder layers swap it in behind
    a config flag.
    """

    d_model: int
    ffn_hidden: int
    num_experts: int
    capacity_factor: float = 1.25
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,
        *,
        valid: jnp.ndarray | None = None,
        deterministic: bool = True,
    ):
        b, s, d = x.shape
        e = self.num_experts
        # Per-SEQUENCE routing groups (the mesh-tf/flaxformer convention):
        # each batch row assigns its own capacity = ceil(cf · s / E) slots
        # per expert, so the dispatch tensor is [b, s, E, C] ~ cf·b·s² —
        # bounded by the sequence length, not (batch·seq)², which at
        # long-context scale is the difference between MBs and GBs.
        capacity = max(int(math.ceil(self.capacity_factor * s / e)), 1)

        # Pad tokens (valid=False) are excluded from routing entirely: they
        # never consume a capacity slot (which would drop real tokens at a
        # far higher rate than capacity_factor implies on padded batches)
        # and never enter the aux-loss statistics. Their FFN output is zero;
        # the surrounding residual carries them.
        if valid is not None and valid.shape != (b, s):
            raise ValueError(
                f"valid must be [batch={b}, seq={s}], got {valid.shape}"
            )
        vf = (
            valid.astype(jnp.float32)
            if valid is not None
            else jnp.ones((b, s), jnp.float32)
        )

        # -- router (float32) ------------------------------------------------
        router_kernel = self.param(
            "router",
            nn.with_partitioning(nn.initializers.lecun_normal(), ("embed", None)),
            (d, e),
        )
        logits = jnp.einsum(
            "bsd,de->bse",
            x.astype(jnp.float32),
            router_kernel.astype(jnp.float32),
        )
        probs = jax.nn.softmax(logits, axis=-1)  # [B, S, E]
        expert_idx = jnp.argmax(probs, axis=-1)  # [B, S] top-1 (Switch)
        gate = jnp.take_along_axis(probs, expert_idx[..., None], axis=-1)[..., 0]
        gate = gate * vf

        # -- capacity assignment (within each row's groups) ------------------
        onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32) * vf[..., None]
        # Slot within the chosen expert's buffer, in token order within the
        # row (exclusive running count of prior same-expert tokens).
        position = (jnp.cumsum(onehot, axis=1) - onehot) * onehot  # [B, S, E]
        pos_in_expert = position.sum(axis=-1).astype(jnp.int32)  # [B, S]
        keep = pos_in_expert < capacity
        gate = jnp.where(keep, gate, 0.0)

        # Dispatch tensor [B, S, E, C]: token (b, s) → (its expert, its slot).
        dispatch = (
            onehot[..., None]
            * jax.nn.one_hot(pos_in_expert, capacity, dtype=jnp.float32)[
                :, :, None, :
            ]
            * keep[..., None, None]
        )

        # -- expert FFNs (batched over the expert dim) ----------------------
        w_up = self.param(
            "w_up",
            nn.with_partitioning(
                nn.initializers.lecun_normal(), ("expert", "embed", "mlp")
            ),
            (e, d, self.ffn_hidden),
        )
        w_down = self.param(
            "w_down",
            nn.with_partitioning(
                nn.initializers.lecun_normal(), ("expert", "mlp", "embed")
            ),
            (e, self.ffn_hidden, d),
        )
        expert_in = jnp.einsum(
            "bsec,bsd->ebcd", dispatch.astype(self.dtype), x.astype(self.dtype)
        )
        h = nn.relu(
            jnp.einsum("ebcd,edf->ebcf", expert_in, w_up.astype(self.dtype))
        )
        h = nn.Dropout(self.dropout, deterministic=deterministic)(h)
        expert_out = jnp.einsum("ebcf,efd->ebcd", h, w_down.astype(self.dtype))

        # -- weighted combine ------------------------------------------------
        combine = dispatch * gate[..., None, None]  # [B, S, E, C]
        out = jnp.einsum(
            "bsec,ebcd->bsd", combine.astype(self.dtype), expert_out
        )

        # -- Switch load-balancing loss -------------------------------------
        # f_e is the fraction of VALID tokens the router chose per expert
        # (pre-drop, the Switch paper's definition); p_e the mean router
        # prob over valid tokens. Drops are a consequence the loss should
        # shrink, not a term that hides imbalance by zeroing overflow.
        n_valid = jnp.maximum(vf.sum(), 1.0)
        frac_routed = onehot.sum(axis=(0, 1)) / n_valid  # f_e
        mean_prob = (probs * vf[..., None]).sum(axis=(0, 1)) / n_valid  # p_e
        aux = e * jnp.sum(frac_routed * mean_prob)
        self.sow("losses", "moe_aux", aux)

        return out


# ---------------------------------------------------------------------------
# Dropless top-k routing over the experts held here
# ---------------------------------------------------------------------------


def route_top_k(logits: jnp.ndarray, k: int, *, renormalize: bool = True):
    """Softmax over all experts in float32, the ``k`` largest a token and
    their weights (renormalised to sum to one where ``renormalize``).
    ``logits [N, E]`` -> ``(probs [N, E], experts [N, k], weights [N, k])``."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, experts = jax.lax.top_k(probs, k)
    if renormalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return probs, experts, weights


# Rows a segment of ``DroplessMoE`` holds, over the N k held / E assignments
# its share expects. At 1.5 the full-size cell (163,840 assignments a layer,
# 32 of 512 experts held: 10,240 expected, 15,360 rows a segment) ran one
# segment in every step read on the chip, at 10,300-10,500 local rows
# (PERF.md section 6, PR 26); a batch with more runs further segments.
_LOCAL_ROWS_SLACK = 1.5


def _grouped_swiglu(xs, sizes, w_gate, w_up, w_down):
    """Rows of ``xs [M, d]`` sorted by expert, ``sizes [held]`` rows an
    expert (rows past their sum belong to no expert: what comes out for
    them is the backend's, zeros on the CPU and garbage on the TPU, and the
    caller masks them):
    ``(silu(x Wg) * (x Wu)) Wd`` with each row's own expert's matrices."""
    dot = functools.partial(
        jax.lax.ragged_dot, group_sizes=sizes,
        preferred_element_type=jnp.float32,
    )
    h = jax.nn.silu(dot(xs, w_gate)) * dot(xs, w_up)
    return dot(h.astype(xs.dtype), w_down)


def route_sigmoid_grouped(logits, bias, k: int, *, groups: int,
                          groups_kept: int, scale: float = 1.0):
    """DeepSeek-V3's router (``noaux_tc``): ``s = sigmoid(logits)`` over all
    experts in float32; the choice reads ``s + bias`` (the correction bias
    balances the load without an auxiliary loss): the experts fall into
    ``groups`` equal groups, each scored by the sum of its two largest ``s +
    bias``, the ``groups_kept`` best groups are kept and the ``k`` largest
    ``s + bias`` among their experts are taken. The weights are the chosen
    experts' ``s`` (not ``s + bias``), divided by their sum, times ``scale``.
    ``logits [N, E]``, ``bias [E]`` -> ``(s [N, E], experts [N, k], weights
    [N, k])``."""
    n, e = logits.shape
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    biased = s + bias.astype(jnp.float32)
    grouped = biased.reshape(n, groups, e // groups)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)  # [N, groups]
    _, kept = jax.lax.top_k(group_score, groups_kept)
    in_kept = jnp.zeros((n, groups), bool).at[
        jnp.arange(n)[:, None], kept
    ].set(True)
    masked = jnp.where(
        jnp.repeat(in_kept, e // groups, axis=-1), biased, -jnp.inf
    )
    _, experts = jax.lax.top_k(masked, k)
    weights = jnp.take_along_axis(s, experts, axis=-1)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True) * scale
    return s, experts, weights


def route(logits, k: int, *, kind: str = "softmax", renormalize: bool = True,
          bias=None, groups: int = 1, groups_kept: int = 1,
          scale: float = 1.0):
    """The router named by ``kind`` (static): ``"softmax"`` is
    ``route_top_k`` (``renormalize``), ``"sigmoid_grouped"`` is
    ``route_sigmoid_grouped`` (``bias``, ``groups``, ``groups_kept``,
    ``scale``). Returns ``(probabilities [N, E], experts [N, k], weights [N,
    k])``."""
    if kind == "softmax":
        return route_top_k(logits, k, renormalize=renormalize)
    if kind == "sigmoid_grouped":
        return route_sigmoid_grouped(
            logits, bias, k, groups=groups, groups_kept=groups_kept, scale=scale
        )
    raise ValueError(f"unknown router kind {kind!r}")


def local_assignments(experts, weights, *, first: int, held: int,
                      num_experts: int) -> dict:
    """The router's choices ``experts [N, k]`` (weights ``[N, k]``) sorted
    by held expert for the grouped products: the assignments of experts
    ``first .. first + held - 1`` first, in expert order, those of absent
    experts last. Returns ``order`` (assignment numbers in that order),
    ``counts [num_experts]`` (assignments an expert, all experts), ``sizes
    [held]``, ``n_local``, ``local`` (whether an assignment is held here),
    and ``token_of`` / ``weight_of`` a sorted assignment (weight 0 where the
    expert is absent)."""
    k = experts.shape[-1]
    flat_expert = experts.reshape(-1) - first           # [N k]
    local = (flat_expert >= 0) & (flat_expert < held)
    sort_key = jnp.where(local, flat_expert, held)
    order = jnp.argsort(sort_key, stable=True)
    counts = jnp.bincount(experts.reshape(-1), length=num_experts)
    sizes = counts[first:first + held].astype(jnp.int32)
    n_local = jnp.sum(sizes)
    token_of = order // k
    weight_of = jnp.where(local, weights.reshape(-1), 0.0)[order]
    return dict(
        order=order, counts=counts, sizes=sizes, n_local=n_local, local=local,
        token_of=token_of, weight_of=weight_of,
    )


def grouped_experts(tokens_c, plan: dict, w_gate, w_up, w_down, *, k: int,
                    num_experts: int):
    """The held experts' part of the layer's output for ``tokens_c [N, d]``
    (the compute dtype) and the sorted assignments ``plan``
    (``local_assignments``). Sorted assignments are taken ``rows`` at a time.
    A layer holding a share expects ``N k held / E`` local assignments and
    sizes a segment at ``_LOCAL_ROWS_SLACK`` times that, so the first segment
    is the step's whole work; a batch with more runs further segments (each
    skipped by ``lax.cond`` while empty), up to all ``N k``. A segment is
    rematerialised, so the loop keeps indices, not activations. Returns
    ``(out [N, d] float32, assignments computed)``: the second adds up,
    inside the segments that ran, the group sizes the grouped products were
    given."""
    n, d = tokens_c.shape
    held = w_gate.shape[0]
    sizes, n_local = plan["sizes"], plan["n_local"]
    full_rows = n * k
    rows = min(
        full_rows,
        -(-int(_LOCAL_ROWS_SLACK * full_rows * held / num_experts) // 256) * 256,
    )
    segments = -(-full_rows // rows)
    pad = segments * rows - full_rows
    token_of = jnp.pad(plan["token_of"], (0, pad))
    weight_of = jnp.pad(plan["weight_of"], (0, pad))
    ends = jnp.cumsum(sizes)
    starts = ends - sizes

    # The ``cond`` sits inside the rematerialised function: around it,
    # the taken branch's residuals (the tokens and the experts'
    # matrices) would become loop-variant outputs and the scan would
    # stack them, once a segment.
    @jax.checkpoint
    def segment(j, tokens_c, w_gate, w_up, w_down):
        lo = j * rows

        def run():
            with jax.named_scope("lm.moe.route"):
                idx = jax.lax.dynamic_slice(token_of, (lo,), (rows,))
                weight = jax.lax.dynamic_slice(weight_of, (lo,), (rows,))
                here = jnp.clip(
                    jnp.minimum(ends, lo + rows) - jnp.maximum(starts, lo), 0
                ).astype(jnp.int32)
                # Rows past the last group belong to no expert. The
                # TPU's grouped product leaves them as it found them,
                # forward and backward (read on the chip, PR 26: garbage
                # of any size, where the CPU's writes zeros), so they
                # are cut off on both sides of it: the ``where`` in
                # front zeroes their cotangent, the one behind their
                # value.
                valid = (lo + jnp.arange(rows) < n_local)[:, None]
                xs = jnp.where(valid, tokens_c[idx], 0)
            with jax.named_scope("lm.moe.experts"):
                ys = _grouped_swiglu(xs, here, w_gate, w_up, w_down)
            ys = jnp.where(valid, ys, 0.0) * weight[:, None]
            return idx, ys, jnp.sum(here)

        def skip():
            return (
                jnp.zeros((rows,), token_of.dtype),
                jnp.zeros((rows, d), jnp.float32),
                jnp.int32(0),
            )

        return jax.lax.cond(lo < n_local, run, skip)

    def add_segment(carry, j):
        out, computed = carry
        idx, ys, grouped = segment(j, tokens_c, w_gate, w_up, w_down)
        with jax.named_scope("lm.moe.route"):
            out = jax.lax.cond(
                j * rows < n_local, lambda o: o.at[idx].add(ys),
                lambda o: o, out,
            )
        return (out, computed + grouped), None

    (out, n_computed), _ = jax.lax.scan(
        add_segment, (jnp.zeros((n, d), jnp.float32), jnp.int32(0)),
        jnp.arange(segments),
    )
    return out, n_computed


def shared_swiglu(tokens_c, w_gate, w_up, w_down, dtype):
    """The always-on shared expert ``(silu(x Wg) * (x Wu)) Wd`` in ``dtype``,
    float32 out; the caller gates it or not."""
    h = jax.nn.silu(tokens_c @ w_gate.astype(dtype)) * (
        tokens_c @ w_up.astype(dtype)
    )
    return (h @ w_down.astype(dtype)).astype(jnp.float32)


class DroplessMoE(nn.Module):
    """Top-k mixture of SwiGLU experts as deployed today: softmax router
    over all ``num_experts``, ``top_k`` experts a token, no capacity and no
    dropped token, one always-on shared expert behind a sigmoid gate.

    **The share.** The layer holds ``experts_held = (first, count)`` of the
    ``num_experts`` (expert parallelism's cut: the chip's experts). It routes
    over all of them, keeps the assignments that fall on its own experts,
    and returns their part of the result plus the shared expert's; what
    absent experts would add is left out, as their chips would add it.
    ``(0, num_experts)`` is the whole layer. Nothing here stands in for the
    exchange between chips.

    **How.** Assignments ``(token, choice)`` are sorted by local expert
    (those of absent experts sort last); ``M`` sorted tokens at a time are
    gathered, three grouped products (``jax.lax.ragged_dot``) apply each
    row's expert, and a weighted scatter-add returns rows to their tokens.
    ``M`` is static: ``_LOCAL_ROWS_SLACK`` times the ``N * top_k * count /
    num_experts`` assignments a share expects. All ``N * top_k`` can be
    local, so the layer loops over as many segments of ``M`` as that takes
    and **skips the empty ones** (``lax.cond`` on the count): the expected
    batch costs one segment, a lopsided one costs more, and none drops a
    token. Two counters say so from either end:
    ``stats["assignments_local"]`` counts the router's choices that name a
    held expert, ``stats["assignments_computed"]`` adds up, inside the
    segments that ran, the group sizes the grouped products were given. A
    segment skipped or cut short shows as a difference between them.

    The routing, the sort and the grouped products are the module's
    functions ``route``, ``local_assignments``, ``grouped_experts`` and
    ``shared_swiglu``, which the served ``models.dsa_lm`` calls too.

    Input ``[B, S, d]``; returns ``(out [B, S, d], stats)`` with float32
    scalars ``aux`` (``E * sum_e f_e p_e`` over all experts, ``f_e`` the share
    of assignments and ``p_e`` the mean router probability),
    ``tokens_held_mean`` / ``tokens_held_max`` (assignments an expert held
    here), ``assignments_local`` and ``assignments_computed``.
    """

    d_model: int
    expert_hidden: int
    shared_hidden: int
    num_experts: int
    top_k: int
    experts_held: tuple[int, int] | None = None
    renormalize: bool = True
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray):
        b, s, d = x.shape
        e, k = self.num_experts, self.top_k
        first, held = self.experts_held or (0, e)
        if not (0 <= first and held > 0 and first + held <= e):
            raise ValueError(f"experts_held {(first, held)} outside 0..{e}")
        n = b * s
        init = nn.initializers.lecun_normal
        router = self.param("router", init(), (d, e))
        stacked = init(batch_axis=(0,))  # the leading axis counts experts
        w_gate = self.param("w_gate", stacked, (held, d, self.expert_hidden))
        w_up = self.param("w_up", stacked, (held, d, self.expert_hidden))
        w_down = self.param("w_down", stacked, (held, self.expert_hidden, d))
        shared_gate = self.param("shared_gate", init(), (d, self.shared_hidden))
        shared_up = self.param("shared_up", init(), (d, self.shared_hidden))
        shared_down = self.param("shared_down", init(), (self.shared_hidden, d))
        shared_router = self.param("shared_router", init(), (d, 1))
        w_gate, w_up, w_down = (
            w.astype(self.dtype) for w in (w_gate, w_up, w_down)
        )
        tokens = x.reshape(n, d)
        tokens_c = tokens.astype(self.dtype)

        with jax.named_scope("lm.moe.route"):
            logits = jnp.dot(
                tokens.astype(jnp.float32), router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            )
            probs, experts, weights = route(
                logits, k, kind="softmax", renormalize=self.renormalize
            )
            plan = local_assignments(
                experts, weights, first=first, held=held, num_experts=e
            )
            counts, sizes, local = plan["counts"], plan["sizes"], plan["local"]
            aux = e * jnp.sum(
                counts.astype(jnp.float32) / (n * k) * jnp.mean(probs, axis=0)
            )

        out, n_computed = grouped_experts(
            tokens_c, plan, w_gate, w_up, w_down, k=k, num_experts=e
        )

        with jax.named_scope("lm.moe.shared"):
            shared = shared_swiglu(
                tokens_c, shared_gate, shared_up, shared_down, self.dtype
            )
            gate = jax.nn.sigmoid(jnp.dot(
                tokens.astype(jnp.float32), shared_router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            ))
            out = out + gate * shared

        held_counts = sizes.astype(jnp.float32)
        stats = {
            "aux": aux,
            "tokens_held_mean": jnp.mean(held_counts),
            "tokens_held_max": jnp.max(held_counts),
            "assignments_local": jnp.sum(local).astype(jnp.float32),
            "assignments_computed": n_computed.astype(jnp.float32),
        }
        return out.reshape(b, s, d).astype(x.dtype), stats
