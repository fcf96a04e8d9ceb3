"""The served decoder: the causal LM of a list of layers (`LayerSpec`)
that the local generation path (`xpacks.llm`) and the slot scheduler
(serving/continuous_batching.py) run. The default list is the plain block
(models/encoder.py).

A layer's kind (`kind_of`) is one module of models/mixers/, looked up in
`KINDS`: its parameter and cache leaves, its part of each program, its
kernels' rules and its device counters are that module's. This module
holds what every kind shares: the parameter tree (a PartitionSpec a leaf,
Megatron-style tensor parallelism over the mesh's `model` axis, data
parallelism over `data`, XLA inserting the all-reduces at the
row-parallel projections), the slot cache (a dict of stacked leaves, the
kinds' own), the layer around the mixer (norms, output gate, routed
experts, feed-forward), and the programs: `prefill_into_slot` over one
prompt and `decode_step_slots` over one token of every slot, each row at
its own position; `generate_serving`'s wave-aligned step is the slot step
with every row at one position.
"""

from __future__ import annotations

import collections
import contextlib
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pathway_tpu.models import routed
from pathway_tpu.models.config import (  # noqa: F401  (this module's names too)
    LatentSpec, LayerSpec, SparseSpec, TransformerConfig, has_experts,
)
from pathway_tpu.models.layers import (
    Array, Counters, Kind, Leaf, Params, Rows, build_mask, ffn, ffn_leaves, rmsnorm,
)
from pathway_tpu.models.mixers import latent, linear, softmax, sparse

# the layer kinds, in the order their cache leaves are allocated and their
# counters ride behind a program's tokens
KINDS: dict[str, Kind] = {
    "global": softmax.GLOBAL,
    "window": softmax.WINDOW,
    "sparse": sparse.SPARSE,
    "linear": linear.LINEAR,
    "latent": latent.LATENT,
}


def kind_of(spec: LayerSpec) -> Kind:
    if spec.mixer != "softmax":
        return KINDS[spec.mixer]
    return KINDS["global" if spec.window is None else "window"]


def _kinds(cfg: TransformerConfig) -> dict[Kind, int]:
    """The kinds of the decoder's layers, in `KINDS`' order, and the layers
    of each."""
    kinds = [kind_of(sp) for sp in cfg.layer_specs]
    return {kind: kinds.count(kind) for kind in KINDS.values() if kind in kinds}


# ------------------------------------------------------------------ params


def _top_leaves(cfg: TransformerConfig) -> dict[str, Leaf]:
    """The leaves outside the blocks. Embeddings shard the vocab/feature
    dim; norms are replicated."""
    d = cfg.d_model
    out = {
        "tok_embed": Leaf((cfg.vocab_size, d), P("model", None), 0, 0.02),
        "ln_f_scale": Leaf((d,), P(None)),
        "head": Leaf((d, cfg.embed_dim or d), P(None, "model"), 2),
    }
    if cfg.learned_positions:
        out["pos_embed"] = Leaf((cfg.max_len, d), P(None, None), 1, 0.02)
    if not cfg.tie_embeddings:
        out["lm_head"] = Leaf(
            (d, cfg.vocab_size), P(None, "model"),
            lambda ks: jax.random.fold_in(ks[2], 1),
        )
    return out


def _block_leaves(cfg: TransformerConfig, spec: LayerSpec) -> dict[str, Leaf]:
    """A layer's leaves: its two norms, its kind's, the q/k norms and the
    output gate where the config has them, its router and experts
    (models/routed.py) and its dense feed-forward."""
    d, kind = cfg.d_model, kind_of(spec)
    out = {
        "ln1_scale": Leaf((d,), P(None)), "ln2_scale": Leaf((d,), P(None)),
        **kind.leaves(cfg, spec),
    }
    if cfg.qk_norm:
        out["q_norm"] = out["k_norm"] = Leaf((cfg.head_dim,), P(None))
    if cfg.out_gate:
        out["gate"] = Leaf(
            (d, kind.heads(cfg)[0] * cfg.head_dim), P(None, "model"),
            lambda ks: jax.random.fold_in(ks[0], 1),
        )
    if has_experts(spec):
        out |= routed.leaves(cfg, spec)
    return out | ffn_leaves(cfg, spec)


def init_params(
    rng: Array, cfg: TransformerConfig, dtype: Any = jnp.float32
) -> Params:
    """Random parameters, each leaf as `_top_leaves` and `_block_leaves`
    declare it; a block draws from six keys of its own. Every leaf is
    drawn in float32 and cast to `dtype` before the next is drawn, so a
    bf16 tree of a 2B-parameter decoder peaks at its own size plus one
    float32 leaf instead of the whole float32 tree (8 GB of a 16 GB chip)."""
    ks = jax.random.split(rng, cfg.n_layers + 3)
    params = {name: leaf.draw(ks, dtype) for name, leaf in _top_leaves(cfg).items()}
    params["blocks"] = []
    for i, spec in enumerate(cfg.layer_specs):
        keys = jax.random.split(ks[3 + i], 6)
        params["blocks"].append({
            name: leaf.draw(keys, dtype)
            for name, leaf in _block_leaves(cfg, spec).items()
        })
    return params


def param_specs(cfg: TransformerConfig) -> Params:
    """PartitionSpecs, as the leaves declare them: tensor-parallel over the
    `model` mesh axis, an experts layer expert-parallel."""
    specs = {name: leaf.spec for name, leaf in _top_leaves(cfg).items()}
    specs["blocks"] = [
        {name: leaf.spec for name, leaf in _block_leaves(cfg, spec).items()}
        for spec in cfg.layer_specs
    ]
    return specs


def shard_params(params: Params, mesh: Mesh, cfg: TransformerConfig) -> Params:
    specs = param_specs(cfg)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )


def count_params(params: Params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def cast_params(params: Params, dtype: Any = jnp.bfloat16) -> Params:
    """bf16-resident inference params: cast once instead of per matmul.

    Training keeps the f32 master copy; serving paths (encode/generate)
    run on the cast tree so weight reads from HBM are half-width and no
    cast ops appear inside the jitted program.
    """
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        params,
    )


# ----------------------------------------------------------- the slot cache
#
# A dict of stacked leaves, each kind's own (`Kind.cache`), every one with
# the slot second: [layers of the kind, slots, ...].
_SLOT_AXIS = 1


def init_kv_cache(cfg: TransformerConfig, batch: int) -> Params:
    cache = {}
    for kind, n in _kinds(cfg).items():
        for name, leaf in kind.cache(cfg, n, batch).items():
            cache[name] = jnp.zeros(leaf.shape, leaf.dtype)
    return cache


def _cache_rows(cfg: TransformerConfig) -> list[tuple[Kind, int]]:
    """Per layer: its kind and its index along its kind's leaves."""
    out, n = [], collections.Counter()
    for sp in cfg.layer_specs:
        kind = kind_of(sp)
        out.append((kind, n[kind]))
        n[kind] += 1
    return out


# ------------------------------------------------------ the device counters


def _counters(cfg: TransformerConfig, step: bool) -> list[Counters]:
    """What a program sends back behind its tokens, in this order: the
    experts' (models/routed.py), each kind's in `KINDS`' order, and last
    a prefill's shares of the router's pairs."""
    kinds = _kinds(cfg)
    experts = bool(cfg.n_expert_layers)
    if step:
        return [routed.STEP] * experts + [kind.step_counters for kind in kinds]
    return (
        [routed.PREFILL] * experts + [kind.prefill_counters for kind in kinds]
        + [routed.SHARES] * routed.has_shares(cfg)
    )


def prefill_counters(cfg: TransformerConfig) -> tuple[str, ...]:
    """The counters `prefill_into_slot` appends to its token, in order."""
    return tuple(name for group in _counters(cfg, False) for name in group.names)


def step_counters(cfg: TransformerConfig) -> tuple[str, ...]:
    """The counters `decode_step_slots` appends to its tokens, in order."""
    return tuple(name for group in _counters(cfg, True) for name in group.names)


# every counter a decoder's programs may send back (ContinuousBatcher's
# `stats` holds each from the start)
COUNTERS = tuple(dict.fromkeys(
    name
    for group in (
        routed.PREFILL, routed.STEP, routed.SHARES,
        *(c for kind in KINDS.values()
          for c in (kind.prefill_counters, kind.step_counters)),
    )
    for name in group.names
))


def _with_counters(tokens: Array, cfg: TransformerConfig, counters: dict,
                   at: Array | None = None) -> Array:
    """The tokens a program returns and, behind them, its counters (a
    step's, where `at` are its slots' positions): they ride to the host in
    the one array the loop reads anyway."""
    tail = [
        v for group in _counters(cfg, at is not None)
        for v in group.values(counters, at)
    ]
    if not tail:
        return tokens
    return jnp.concatenate([tokens, jnp.stack(tail).astype(jnp.int32)])


# --------------------------------------------------------------- the layer


def _lm_logits(hline: Array, params: Params, cfg: TransformerConfig) -> Array:
    with jax.named_scope("logits"):
        if cfg.logit_scale != 1.0:
            hline = (hline * cfg.logit_scale).astype(hline.dtype)
        if cfg.tie_embeddings:
            return jnp.einsum(
                "bsd,vd->bsv", hline, params["tok_embed"].astype(cfg.dtype),
                preferred_element_type=jnp.float32,
            )
        return jnp.einsum(
            "bsd,dv->bsv", hline, params["lm_head"].astype(cfg.dtype),
            preferred_element_type=jnp.float32,
        )


def _embed(params: Params, token: Array, cfg: TransformerConfig) -> Array:
    x = params["tok_embed"].astype(cfg.dtype)[token]
    if cfg.embed_scale != 1.0:
        x = (x * cfg.embed_scale).astype(cfg.dtype)
    return x


def _branch(x: Array, y: Array, cfg: TransformerConfig) -> Array:
    """The residual stream plus a branch's output."""
    if cfg.residual_scale != 1.0:
        y = (y * cfg.residual_scale).astype(y.dtype)
    return x + y


def _layer(x, block, spec, li, rows, mix, branch=None):
    """One decoder layer over rows x [b, s, d]. `mix` is its kind's
    `prefill` or `step`: from the normed rows, the mixer's output, the
    layer's cache leaves written (`li`: its index along them). An experts
    layer appends its per-expert counts of live pairs to
    `rows.counters["experts"]`. Returns the rows and the expert branch in
    flight: what a layer whose `shortcut` is "start" computed from its
    normed rows, which the next "land" adds beside its feed-forward
    (`branch` is what came in)."""
    cfg = rows.cfg
    eps = cfg.norm_eps
    if spec.ff == "experts" and cfg.router == "chosen":
        idx, w = routed.route(x, block, cfg)
    xin = rmsnorm(x, block["ln1_scale"], eps)
    ctx = mix(xin, block, spec, li, rows)
    with jax.named_scope("attn"):
        if cfg.out_gate:
            with jax.named_scope("gate"):
                gate = jax.nn.sigmoid(jnp.einsum(
                    "bsd,de->bse", xin, block["gate"].astype(cfg.dtype),
                    preferred_element_type=jnp.float32,
                ))
                ctx = (ctx * gate).astype(cfg.dtype)
        x = _branch(x, jnp.einsum(
            "bsd,de->bse", ctx, block["o"].astype(cfg.dtype),
            preferred_element_type=jnp.float32,
        ).astype(cfg.dtype), cfg)
    u = rmsnorm(x, block["ln2_scale"], eps)
    if has_experts(spec):
        started = spec.shortcut == "start"  # the branch lands a layer on
        with jax.named_scope("shortcut") if started else contextlib.nullcontext():
            if cfg.router == "all":
                idx, w = routed.route(u, block, cfg)
            y, counts = routed.experts(u, idx, w, rows.live, block, cfg)
        if routed.has_shares(cfg):  # the held experts' counts, then the router's
            rows.counters["shares"].append(counts[cfg.held[1]:])
            counts = counts[:cfg.held[1]]
        rows.counters["experts"].append(counts)
        if not started:
            return _branch(x, y, cfg), branch
        branch = y
    x = _branch(x, ffn(u, block, cfg), cfg)
    if spec.shortcut == "land":
        with jax.named_scope("shortcut"):
            x, branch = _branch(x, branch, cfg), None
    return x, branch


def _layers(x: Array, params: Params, rows: Rows, program: str) -> Array:
    """Every layer over rows x, its mixer its kind's `program` ("prefill"
    or "step")."""
    branch = None  # a shortcut's expert branch, from its start to its landing
    for (kind, li), spec, block in zip(
        _cache_rows(rows.cfg), rows.cfg.layer_specs, params["blocks"]
    ):
        x, branch = _layer(x, block, spec, li, rows, getattr(kind, program), branch)
    return x


# ---------------------------------------------------------------- programs


def _step_rows(
    params: Params, cache: Params, token: Array, pos: Array, pad_len: Array,
    cfg: TransformerConfig,
):
    """One token of every row, each row at its own physical position `pos`
    [b] behind its own left pad `pad_len` [b]: writes the row's key and
    value, attends over [pad_len, pos] (a window layer over its ring, a
    sparse layer over the blocks it chooses; a linear layer moves its state
    on by one position). Returns (logits [b, vocab], cache, what the layers
    counted: `Rows.counters`)."""
    x = _embed(params, token, cfg)[:, None, :]
    logical = (pos - pad_len)[:, None]
    if cfg.learned_positions:
        x = x + params["pos_embed"].astype(cfg.dtype)[pos - pad_len][:, None, :]
    rows = Rows(
        cfg=cfg, cache=cache, counters=collections.defaultdict(list),
        pos=logical, at=pos, pad=pad_len, fused=False,
    )
    for setup in dict.fromkeys(type(kind).step_setup for kind in _kinds(cfg)):
        setup(rows)
    rows.live = (pos > 0)[:, None]  # a free slot's vectors are zeros
    x = _layers(x, params, rows, "step")
    hline = rmsnorm(x, params["ln_f_scale"], cfg.norm_eps)
    return _lm_logits(hline, params, cfg)[:, 0, :], cache, rows.counters


def _prefill(
    params: Params, prompt_ids: Array, cache: Params, cfg: TransformerConfig,
    prompt_mask: Array | None,
):
    """One batched causal forward over the whole prompt, writing every
    layer's cache leaves: ONE XLA program over [b, p]. With `prompt_mask`
    the batch is LEFT-padded (real tokens end at p-1, at positions 0..len-1
    by the mask's cumsum; `generate`). Returns (last-position logits
    [b, vocab], cache, what the layers counted: `Rows.counters`)."""
    b, p = prompt_ids.shape
    x = _embed(params, prompt_ids, cfg)
    if prompt_mask is None:
        valid = jnp.ones((b, p), jnp.int32)
        pos_idx = jnp.broadcast_to(jnp.arange(p)[None, :], (b, p))
        if cfg.learned_positions:
            x = x + params["pos_embed"].astype(cfg.dtype)[None, :p, :]
    else:
        valid = prompt_mask
        pos_idx = jnp.clip(jnp.cumsum(prompt_mask, axis=1) - 1, 0, None)
        if cfg.learned_positions:
            x = x + params["pos_embed"].astype(cfg.dtype)[pos_idx]
    rows = Rows(
        cfg=cfg, cache=cache, counters=collections.defaultdict(list),
        valid=valid, pos=pos_idx, width=p,
        # the plain attention's (where a kernel masks inside, none reads it)
        mask=build_mask(valid, causal=True),
    )
    for setup in dict.fromkeys(type(kind).prefill_setup for kind in _kinds(cfg)):
        setup(rows)
    rows.live = valid.astype(bool)
    rows.fused, rows.rope = softmax.rowwise_uses_kernel(cfg, p), None
    if rows.fused and any(sp.pos == "rotary" for sp in cfg.layer_specs):
        from pathway_tpu.ops.rowwise import rope_tables

        with jax.named_scope("rope"):  # once, for every rotary layer
            rows.rope = rope_tables(pos_idx, cfg.rope_theta, cfg.head_dim)
    x = _layers(x, params, rows, "prefill")
    hlast = rmsnorm(x[:, -1:, :], params["ln_f_scale"], cfg.norm_eps)
    return _lm_logits(hlast, params, cfg)[:, 0, :], cache, rows.counters


def generate(
    params: Params,
    prompt_ids: Array,  # [b, p]
    n_steps: int,
    cfg: TransformerConfig,
    temperature: float = 0.0,
    rng: Array | None = None,
    prompt_mask: Array | None = None,  # [b, p] 1/0, LEFT-padded batches
) -> Array:
    """Batched prefill + `lax.scan` decode. Returns [b, p + n_steps].

    `prompt_mask` enables serving-style batching of heterogeneous
    prompts: left-pad every prompt to a common length, pass the validity
    mask, and each row generates exactly what an unpadded single-prompt
    run would (mask-cumsum positions; pad slots never attend)."""
    toks, _cache = generate_serving(
        params, prompt_ids, init_kv_cache(cfg, prompt_ids.shape[0]),
        n_steps, cfg, temperature=temperature, rng=rng,
        prompt_mask=prompt_mask,
    )
    return toks


def generate_serving(
    params: Params,
    prompt_ids: Array,  # [b, p]
    cache: Params,  # KV cache for batch b (init_kv_cache shape)
    n_steps: int,
    cfg: TransformerConfig,
    temperature: float = 0.0,
    rng: Array | None = None,
    prompt_mask: Array | None = None,
) -> tuple[Array, Params]:
    """`generate` for the serving loop: the KV cache is an ARGUMENT and
    is returned, so a dispatch site can keep one persistent cache buffer
    per batch bucket and jit with `donate_argnums` on it — XLA then
    reuses the (hundreds of MB at Gemma shapes) allocation in place
    across dispatches instead of re-allocating per call. Stale cache
    contents from a previous wave are harmless: prefill rewrites
    positions 0..p-1, decode writes p..p+n-1, and the attention masks
    never read past the current position."""
    b, p = prompt_ids.shape
    if p + n_steps > cfg.max_len:
        raise ValueError(
            f"prompt ({p}) + n_steps ({n_steps}) exceeds max_len ({cfg.max_len})"
        )
    if temperature > 0.0 and rng is None:
        raise ValueError("sampled generation (temperature > 0) requires rng")
    first_logits, cache, _ = _prefill(params, prompt_ids, cache, cfg, prompt_mask)
    pad_len = (
        jnp.zeros((b,), jnp.int32)
        if prompt_mask is None
        else (p - jnp.sum(prompt_mask, axis=1)).astype(jnp.int32)
    )

    def pick(lg: Array, key):
        if temperature > 0.0:
            key, sub = jax.random.split(key)
            return jax.random.categorical(sub, lg / temperature).astype(jnp.int32), key
        return jnp.argmax(lg, -1).astype(jnp.int32), key

    key = rng
    first_tok, key = pick(first_logits, key)

    def body(carry, i):
        cache, tok, key = carry
        lg, cache, _ = _step_rows(
            params, cache, tok, jnp.full((b,), p + i, jnp.int32), pad_len, cfg
        )
        nxt, key = pick(lg, key)
        # emit the token being consumed this step; the carry holds the next
        return (cache, nxt, key), tok

    (cache, _last_tok, _), toks = jax.lax.scan(
        body, (cache, first_tok, key), jnp.arange(n_steps)
    )
    return jnp.concatenate([prompt_ids, toks.T], axis=1), cache


def prefill_into_slot(
    params: Params,
    prompt_ids: Array,  # [1, P] LEFT-padded (pad_left_rows convention)
    prompt_mask: Array,  # [1, P] 1/0
    cache: Params,  # multi-slot serving cache (init_kv_cache shape)
    slot: Array,  # scalar int32 — which cache row this request owns
    cfg: TransformerConfig,
) -> tuple[Array, Params]:
    """Prefill ONE request into row `slot` of a multi-slot serving cache
    (continuous batching). Runs the standard b=1 left-padded prefill into
    a scratch single-row cache and scatters that row into `cache` at the
    slot. `slot` is a traced scalar, so one compiled program serves every
    slot of the bucket — a request joining an in-flight batch costs zero
    new XLA compilations once its prompt bucket is warm. Returns (first
    decoded token [1] int32, cache); argmax decoding, matching the
    temperature-0 `generate_serving` path bit for bit per row. The
    token has `prefill_counters` behind it."""
    lg, mini, counters = _prefill(
        params, prompt_ids, init_kv_cache(cfg, 1), cfg, prompt_mask
    )
    with jax.named_scope("cache_write"):
        for name, row in mini.items():
            # the slot's whole row of every leaf, whatever its axes: nothing
            # of the slot's last request stays
            at = [0] * row.ndim
            at[_SLOT_AXIS] = slot
            cache[name] = jax.lax.dynamic_update_slice(cache[name], row, at)
    with jax.named_scope("logits"):
        first = jnp.argmax(lg, -1).astype(jnp.int32)
    return _with_counters(first, cfg, counters), cache


def decode_step_slots(
    params: Params,
    cache: Params,
    token: Array,  # [b] int32 — the token each slot consumes this step
    pos: Array,  # [b] int32 — per-slot physical write position
    pad_len: Array,  # [b] int32 — per-slot left-pad length
    cfg: TransformerConfig,
) -> tuple[Array, Params]:
    """One decode step where every batch row is an INDEPENDENT request at
    its own sequence position (continuous batching). Unlike
    `generate_serving`'s step, which advances a wave-aligned batch at one
    shared scalar position, here `token`/`pos`/`pad_len` are per-row vectors: row
    i consumes ``token[i]``, writes its K/V at physical position
    ``pos[i]`` of its own cache slot, and attends over
    ``[pad_len[i], pos[i]]`` — its left-padded prompt plus the tokens it
    has decoded so far. Rows never read each other's slots, so a freshly
    prefilled request is correct from its first step even though its
    neighbours are mid-generation. Returns (next token [b] int32, cache);
    argmax decoding, bit-identical per row to the wave-aligned path. The
    tokens have `step_counters` behind them."""
    lg, cache, counters = _step_rows(params, cache, token, pos, pad_len, cfg)
    with jax.named_scope("logits"):
        nxt = jnp.argmax(lg, -1).astype(jnp.int32)
    return _with_counters(nxt, cfg, counters, pos), cache
