"""Routed experts: the router, the grouped products of the experts held
here (ops/experts.py's kernels where `experts_use_kernel` holds), their
parameter leaves and the device counters an experts decoder's programs
send back behind their tokens."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from pathway_tpu.models.config import LayerSpec, TransformerConfig
from pathway_tpu.models.layers import Array, Counters, Leaf, Params, kernel_may_run


def has_shares(cfg: TransformerConfig) -> bool:
    """Whether the router chooses among more than the experts held here:
    a share of them, or identity experts beside them."""
    return bool(cfg.n_expert_layers) and (
        cfg.experts_held is not None or cfg.n_zero_experts > 0
    )


def leaves(cfg: TransformerConfig, spec: LayerSpec) -> dict[str, Leaf]:
    """The router and the matrices of the experts held here, [count, ...],
    expert-parallel: the expert axis is the sharded one. The router has an
    output for every expert there is, and the identity ones."""
    d, e, fe = cfg.d_model, cfg.held[1], cfg.d_expert or cfg.d_ff
    n_out = cfg.n_experts + cfg.n_zero_experts
    kg, ku, kd = 2, 5, 3
    if spec.shortcut == "start":  # the layer's own feed-forward draws from those
        kg, ku, kd = (
            lambda ks, i=i: jax.random.fold_in(ks[i], 1) for i in (kg, ku, kd)
        )
    by_expert = P("model", None, None)
    out = {
        "router": Leaf((d, n_out), P(None, None), 4),
        "expert_gate": Leaf((e, d, fe), by_expert, kg),
        "expert_up": Leaf((e, d, fe), by_expert, ku),
        "expert_down": Leaf((e, fe, d), by_expert, kd),
    }
    if cfg.router_bias:
        out["router_bias"] = Leaf((n_out,), P(None), fill=0.0, dtype=jnp.float32)
    return out


def route(x: Array, block: Params, cfg: TransformerConfig):
    """The router, in float32: per token its n_active experts and their
    weights. `cfg.router` "chosen": on the layer's input (before the
    attention's norm), the weights the softmax over the chosen logits.
    "all": on the normed rows the experts read, the scores a softmax over
    every output; the largest of score + `router_bias` are chosen, and a
    chosen expert's weight is its score (without the bias) times
    `router_scale`, not renormalised."""
    with jax.named_scope("router"):
        logits = jnp.einsum(
            "bsd,de->bse", x.astype(jnp.float32),
            block["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        if cfg.router == "chosen":
            top, idx = jax.lax.top_k(logits, cfg.n_active)
            return idx, jax.nn.softmax(top, axis=-1)
        scores = jax.nn.softmax(logits, axis=-1)
        by = scores + block["router_bias"] if cfg.router_bias else scores
        _, idx = jax.lax.top_k(by, cfg.n_active)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        return idx, (w * cfg.router_scale if cfg.router_scale != 1.0 else w)



def experts(u: Array, idx: Array, w: Array, live: Array, block: Params,
             cfg: TransformerConfig):
    """Routed ReGLU experts over normed rows u [b, s, d]: the token-expert
    pairs are sorted by expert and each expert multiplies its own run of
    rows (a grouped product), whatever the run's length, so no pair is ever
    dropped. `live` [b, s] marks the rows that count. Returns the layer's
    output and how many live pairs each expert got [n_experts].

    Where `experts_use_kernel` holds the products are ops/experts.py
    `grouped_experts` (gate and up in one kernel with the ReLU product, the
    pair's weight in the down kernel) and the combine is its
    `combine_experts` (a token's rows fetched by index and summed);
    elsewhere three `ragged_dot` and the weighted sum. Products accumulate
    in float32 and a token's pairs are summed in float32 on both.

    Where the router chooses among more than the experts held here
    (`has_shares`), `_experts_held`: the same products over the pairs of
    the held experts alone, and what it counted beside them."""
    if has_shares(cfg):
        return _experts_held(u, idx, w, live, block, cfg)
    with jax.named_scope("experts"):
        b, s, d = u.shape
        k, e = cfg.n_active, cfg.n_experts
        flat = idx.reshape(-1)  # the pairs, token-major
        # the pairs by expert, and their weights carried along by the sort
        _, order, by_expert_w = jax.lax.sort(
            (flat, jnp.arange(flat.size, dtype=jnp.int32), w.reshape(-1)),
            num_keys=1, is_stable=True,
        )
        # each expert's pairs, and those of live rows: one comparison and
        # two sums, not two scatters of every pair into the bins
        hit = flat[:, None] == jnp.arange(e, dtype=flat.dtype)
        sizes = jnp.sum(hit, axis=0, dtype=jnp.int32)
        counts = jnp.sum(
            hit & jnp.repeat(live.reshape(-1), k)[:, None], axis=0,
            dtype=jnp.int32,
        )
        rows = u.reshape(-1, d)[order // k]  # each pair's token, by expert
        # each pair's place there, a token's k side by side in [k, tokens]:
        # rows gathered by it are k slabs of whole [tokens, d] tiles, where
        # [tokens, k, d] pads k to a sublane tile in a pass of its own
        back = jnp.argsort(order).reshape(b * s, k).T
        if experts_use_kernel(cfg, b * s * k):
            # imported where it is traced: Pallas loads when a program
            # first needs it
            from pathway_tpu.ops.experts import combine_experts, grouped_experts

            # leaves of the activations' dtype (a served decoder's) are the
            # kernels' own operands, read where they lie: the cast is none
            y = grouped_experts(
                rows, by_expert_w, sizes,
                *(block[name].astype(cfg.dtype)
                  for name in ("expert_gate", "expert_up", "expert_down")),
                act=cfg.expert_act,
            )  # [pairs, d / 128, 128] float32, weighted, by expert
            y = combine_experts(y, back, cfg.dtype)
        else:
            y = _ragged(rows, sizes, lambda name: block[name].astype(cfg.dtype), cfg)
            y = jnp.einsum("ktd,tk->td", y[back], w.reshape(-1, k))
        return y.astype(cfg.dtype).reshape(b, s, d), counts


_EXPERT_ACTS = {"relu": jax.nn.relu, "silu": jax.nn.silu}


def _ragged(rows: Array, sizes: Array, leaf, cfg: TransformerConfig) -> Array:
    """The experts' products by three `ragged_dot`: rows sorted by expert,
    `sizes` of them each, and the matrix `leaf` gives of each name ->
    [rows, d] float32, by expert."""
    def grouped(x: Array, name: str) -> Array:
        return jax.lax.ragged_dot(
            x, leaf(name), sizes, preferred_element_type=jnp.float32
        )

    hidden = (
        _EXPERT_ACTS[cfg.expert_act](grouped(rows, "expert_gate"))
        * grouped(rows, "expert_up")
    ).astype(cfg.dtype)
    return grouped(hidden, "expert_down")
# pairs a pass of `_experts_held` multiplies: more than an even router
# sends to 16 of 768 outputs from a prompt of 10,240 tokens and 12 picks
# (2,560), so that the first pass, which stands outside the loop, is as a
# rule the only one; a step's few pairs all go through in it
_HELD_CHUNK = 4096


def _experts_held(u: Array, idx: Array, w: Array, live: Array, block: Params,
                  cfg: TransformerConfig):
    """This chip's share of a routed expert layer over normed rows u
    [b, s, d]: the router chose among `n_experts` experts, of which the
    matrices of `cfg.held` = (first, count) are here, and
    `n_zero_experts` identity experts (an index past n_experts). A pair
    whose expert is held is computed; an identity pick adds weight x u, with
    no product; a pair whose expert lies on another chip adds nothing here
    (that chip computes it), and nothing stands in for it.

    The pairs are sorted by held expert, the others behind them, and only
    the held ones are multiplied: in passes of `_HELD_CHUNK` pairs, one
    always and then as many more as the held pairs fill (a loop whose
    length is the router's, so no pair is dropped however uneven it is;
    the first pass stands outside it, where a trace names its products by
    the leaves they read). A pass is the grouped product of
    `experts`, by the kernels where `experts_use_kernel` holds of its
    pairs; its rows, weighted, are added to their tokens in float32.

    Returns the output and the counts [count + 3] int32 of live pairs: each
    held expert's, then the router's pairs, the identity picks and the
    pairs of absent experts."""
    with jax.named_scope("experts"):
        b, s, d = u.shape
        k, (first, count) = cfg.n_active, cfg.held
        t = b * s
        flat = idx.reshape(-1)  # the pairs, token-major
        real = flat < cfg.n_experts
        here = real & (flat >= first) & (flat < first + count)
        local = jnp.where(here, flat - first, count)  # count: not held here
        _, order, by_expert_w = jax.lax.sort(
            (local, jnp.arange(flat.size, dtype=jnp.int32), w.reshape(-1)),
            num_keys=1, is_stable=True,
        )
        alive = jnp.repeat(live.reshape(-1), k)
        hit = local[:, None] == jnp.arange(count, dtype=local.dtype)
        sizes = jnp.sum(hit, axis=0, dtype=jnp.int32)
        counts = jnp.sum(hit & alive[:, None], axis=0, dtype=jnp.int32)
        n_held = jnp.sum(sizes)
        edges = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])
        chunk = min(_HELD_CHUNK, t * k)
        kernel = experts_use_kernel(cfg, chunk)
        leaves = {
            name: block[name].astype(cfg.dtype)
            for name in ("expert_gate", "expert_up", "expert_down")
        }
        flat_u = u.reshape(t, d)
        # the sorted order, with a pass's room behind it: the last pass may
        # hang over the pairs there are
        order = jnp.pad(order, (0, chunk))
        by_expert_w = jnp.pad(by_expert_w, (0, chunk))

        def one(lo, acc):
            at = jax.lax.dynamic_slice(order, (lo,), (chunk,))
            token = at // k
            held = lo + jnp.arange(chunk, dtype=jnp.int32) < n_held
            wt = jnp.where(
                held, jax.lax.dynamic_slice(by_expert_w, (lo,), (chunk,)), 0.0
            )
            rows = flat_u[token]
            part = jnp.clip(edges[1:], lo, lo + chunk) - jnp.clip(
                edges[:-1], lo, lo + chunk
            )  # each held expert's rows of this pass
            if kernel:
                # imported where it is traced: Pallas loads when a program
                # first needs it
                from pathway_tpu.ops.experts import grouped_experts

                # the kernels' groups fill their rows: what hangs over the
                # held pairs goes to the last expert, at weight 0
                part = part.at[-1].add(chunk - jnp.sum(part))
                y = grouped_experts(
                    rows, wt, part, *leaves.values(), act=cfg.expert_act
                ).reshape(chunk, d)  # float32, weighted
            else:
                y = _ragged(rows, part, leaves.__getitem__, cfg) * wt[:, None]
            # a token's pairs, summed in float32 where the token lies
            return acc.at[jnp.where(held, token, t)].add(y, mode="drop")

        y = one(jnp.zeros((), jnp.int32), jnp.zeros((t, d), jnp.float32))
        if chunk < t * k:  # more pairs than a pass holds
            _, y = jax.lax.while_loop(
                lambda carry: carry[0] < n_held,
                lambda carry: (carry[0] + chunk, one(*carry)),
                (jnp.full((), chunk, jnp.int32), y),
            )
        if cfg.n_zero_experts:
            with jax.named_scope("zero_experts"):
                w_zero = jnp.sum(
                    jnp.where(real.reshape(t, k), 0.0, w.reshape(t, k)), axis=1
                )
                y = y + w_zero[:, None] * flat_u.astype(jnp.float32)
        tail = jnp.stack([
            jnp.sum(alive, dtype=jnp.int32),
            jnp.sum(~real & alive, dtype=jnp.int32),
            jnp.sum(real & ~here & alive, dtype=jnp.int32),
        ])
        return (
            y.astype(cfg.dtype).reshape(b, s, d), jnp.concatenate([counts, tail])
        )


# a visit of ops/experts.py's kernels fetches an expert's matrices and runs
# whole blocks of 128 rows: with fewer pairs an expert than that most of a
# block is masked. Measured at a prefill's 960 an expert (twice as fast as
# `ragged_dot`); a decode step's 6 a slot stay on `ragged_dot`, which reads
# each touched expert once (PERF.md section 6)
_EXPERT_KERNEL_PAIRS = 128
# the combine kernel's row indices, one int32 a pair, ride in the chip's
# scalar memory (1 MiB on a v5e; the compiler refuses 65,536 x 6)
_EXPERT_KERNEL_MAX_PAIRS = 196_608
# a visit holds one expert's gate and up matrices whole, double-buffered,
# in the chip's fast memory (128 MiB on a v5e, of which the kernels ask
# 100): 15.7 MB at widths of 2,560 x 768, 100.7 MB at 6,144 x 2,048, which
# the compiler refuses. Until the kernels tile an expert's width, experts
# that large stay on `ragged_dot`
_EXPERT_KERNEL_MATRIX_BYTES = 64 << 20


def experts_use_kernel(cfg: TransformerConfig, pairs: int) -> bool:
    """Whether a grouped product over `pairs` token-expert pairs of the
    experts held here runs ops/experts.py `grouped_experts` (and, where
    every expert is held, `combine_experts`) and not three `ragged_dot` and
    a weighted sum: where `kernel_may_run`, with model and expert widths of
    a multiple of 128 lanes, `_EXPERT_KERNEL_PAIRS` pairs an expert HELD at least
    (`cfg.held`: all of them, or this chip's share), which a prefill has
    and a decode step has not, no more than `_EXPERT_KERNEL_MAX_PAIRS`
    in all, and an expert's gate and up matrices that fit the chip's fast
    memory twice over (`_EXPERT_KERNEL_MATRIX_BYTES`)."""
    return (
        kernel_may_run(cfg)
        and cfg.d_model % 128 == 0
        and (cfg.d_expert or cfg.d_ff) % 128 == 0
        and _EXPERT_KERNEL_PAIRS * cfg.held[1] <= pairs <= _EXPERT_KERNEL_MAX_PAIRS
        and 4 * cfg.d_model * (cfg.d_expert or cfg.d_ff)
        * jnp.dtype(cfg.dtype).itemsize <= _EXPERT_KERNEL_MATRIX_BYTES
    )


def prefill_experts_use_kernel(cfg: TransformerConfig, width: int) -> bool:
    """Whether the experts layers of a prefill of prompts `width` wide, one
    row, run the kernel: `experts_use_kernel` of the pairs one grouped
    product sees, for a decoder that has such layers. Where every expert is
    held that is all the prefill's pairs; where a share is held
    (`_experts_held`) a pass of `_HELD_CHUNK` pairs of the held experts."""
    pairs = width * cfg.n_active
    if has_shares(cfg):
        pairs = min(_HELD_CHUNK, pairs)
    return bool(cfg.n_expert_layers) and experts_use_kernel(cfg, pairs)


# what an experts decoder's two programs send back, first behind their
# tokens, summed over the expert layers (`counters["experts"]`: each
# layer's per-expert counts of live pairs, of the experts held here): a
# prefill's token-expert pairs of the real tokens and its fullest expert's
# pairs; a step's distinct experts that the occupied rows (`at` > 0) hit,
# and the layers so counted
PREFILL = Counters(("routed_pairs", "expert_load_max"), lambda counters, at: [
    sum(c.sum() for c in counters["experts"]),
    sum(c.max() for c in counters["experts"]),
])
STEP = Counters(("experts_touched", "moe_layers_run"), lambda counters, at: [
    sum((c > 0).sum() for c in counters["experts"]),
    len(counters["experts"]) * jnp.any(at > 0).astype(jnp.int32),
])
# and what a prefill sends last where the router chooses among more than
# the experts held here (`has_shares`), summed over the real tokens and the
# expert layers: every pair the router made (tokens x n_active), those that
# chose an identity expert, and those whose expert lies on another chip.
# `routed_pairs` are then the pairs computed here, and the three add up to
# `router_pairs`
SHARES = Counters(
    ("router_pairs", "zero_pairs", "absent_pairs"),
    lambda counters, at: list(sum(counters["shares"])),
)
