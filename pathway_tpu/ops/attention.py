"""Fused multi-head attention kernels for the TPU numeric plane.

The flagship embedder runs many short sequences (RAG chunks, seq <= 128)
at large batch. XLA's stock lowering of that shape materializes the
[b, h, q, k] score tensor in HBM and inserts relayout copies between the
fused qkv projection and the per-head batched matmuls — measured ~17 ms
per layer at (b=4096, s=64, h=6, dh=64) on v5e, ~7x the bandwidth floor.

`fused_qkv_attention` is a Pallas kernel that takes the *fused* qkv
projection output [b, s, 3*d] straight from the MXU, does the head
split, scores, masked softmax, and value contraction entirely in VMEM,
and writes only ctx [b, s, d] back to HBM. Traffic per call is the
read of qkv and the write of ctx — nothing else.

`prefill_attention` is the decoder's: causal attention of a whole prompt
to itself for every layer kind of `LayerSpec` (every earlier position or
a window; any grouping of query heads over key heads), tiled with a
streaming softmax so that no score reaches HBM, and skipping by whole
tiles what the causal order, the window and the padding rule out.
models/mixers/softmax.py `prefill_uses_kernel` says which prefills run it.

`decode_attention` is the decode step's: one query a slot over the slot
cache, whose stacked leaf ([layers, slots, kv heads, rows, head]) is the
kernel's operand and result as it lies. It writes the step's own row and
fetches only the tiles that hold a live row of the slot, so a step reads
what its slots have decoded, not what the cache has room for.
models/mixers/softmax.py `step_uses_kernel` says which steps run it.

Reference parity: replaces the torch SDPA used by the reference's local
embedding models (`/root/reference/python/pathway/xpacks/llm/embedders.py:270`
runs SentenceTransformer → torch attention); this is the TPU-native
equivalent of that hot loop.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _attn_kernel(qkv_ref, bias_ref, out_ref, *, n_heads: int, head_dim: int,
                 scale: float):
    """One grid step: a [B, s, 3d] qkv block -> [B, s, d] context block.

    Head loop is a static Python loop (n_heads is small); each head does
    two B-batched (s x dh) matmuls with f32 accumulation and a VMEM-local
    f32 softmax. `bias_ref` is an additive key-axis mask [B, s] (0 for
    valid, -1e30 for padding).
    """
    d = n_heads * head_dim
    qkv = qkv_ref[:]  # [B, s, 3d] bf16
    bias = bias_ref[:]  # [B, s] f32
    bnum = qkv.shape[0]
    s = qkv.shape[1]
    batch_dot = functools.partial(
        jax.lax.dot_general,
        dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    for hi in range(n_heads):
        lo = hi * head_dim
        q = qkv[:, :, lo:lo + head_dim]
        k = qkv[:, :, d + lo:d + lo + head_dim]
        v = qkv[:, :, 2 * d + lo:2 * d + lo + head_dim]
        scores = batch_dot(q, k) * scale + bias[:, None, :]  # [B, s, s] f32
        m = jnp.max(scores, axis=-1, keepdims=True)
        e = jnp.exp(scores - m)
        probs = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(qkv.dtype)
        # ctx: [B, s, dh] — contraction over the key axis
        ctx = jax.lax.dot_general(
            probs, v,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        out_ref[:, :, lo:lo + head_dim] = ctx.astype(out_ref.dtype)


def fused_qkv_attention(
    qkv: jax.Array,  # [b, s, 3*d] fused projection output
    token_mask: jax.Array,  # [b, s] 1/0
    n_heads: int,
    *,
    block_b: int = 16,
    interpret: bool = False,
) -> jax.Array:
    """Bidirectional MHA over a fused qkv tensor; returns ctx [b, s, d].

    VMEM per grid step: the qkv block (block_b * s * 3d * 2 B) and the
    ctx block (a third of that), each double-buffered by the pipeline,
    plus one head's f32 scores [block_b, s, s] and their exp/probs. At
    the largest serving bucket (block_b=16, s=128, d=384) that is
    9.4 MB + 3.1 MB + ~3 MB. Mosaic (libtpu 0.0.34, TPU v5e) compiles
    that and every smaller bucket — s in 16..128, batch 8..4096, where
    batch 8 halves block_b to 8 — and `chip_smoke.py` checks each
    against `reference_attention` on the chip.
    """
    b, s, d3 = qkv.shape
    d = d3 // 3
    head_dim = d // n_heads
    scale = 1.0 / math.sqrt(head_dim)
    while b % block_b != 0:
        block_b //= 2
    bias = jnp.where(token_mask == 0, -1e30, 0.0).astype(jnp.float32)
    kernel = functools.partial(
        _attn_kernel, n_heads=n_heads, head_dim=head_dim, scale=scale
    )
    return pl.pallas_call(
        kernel,
        grid=(b // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, s, d3), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, s), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, s, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, d), qkv.dtype),
        interpret=interpret,
    )(qkv, bias)


def reference_attention(
    qkv: jax.Array, token_mask: jax.Array, n_heads: int
) -> jax.Array:
    """Plain-XLA einsum attention over the same fused-qkv contract —
    the path off the TPU and the numerical reference for tests."""
    b, s, d3 = qkv.shape
    d = d3 // 3
    dh = d // n_heads
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(b, s, n_heads, dh)
    k = k.reshape(b, s, n_heads, dh)
    v = v.reshape(b, s, n_heads, dh)
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) / math.sqrt(dh)
    scores = jnp.where(token_mask[:, None, None, :] == 0, -1e30, scores)
    probs = jax.nn.softmax(scores, axis=-1).astype(qkv.dtype)
    ctx = jnp.einsum(
        "bhqk,bkhd->bqhd", probs, v, preferred_element_type=jnp.float32
    ).astype(qkv.dtype)
    return ctx.reshape(b, s, d)


# ------------------------------------------------ causal prefill attention
#
# Written in XLA, a prefill's float32 scores [heads, p, p] cross HBM several
# times: a third to a half of a prefill's device time on v5e (PERF.md
# section 5). The kernel below never writes them.

_MASKED = -1e30  # the score of a pair that is not allowed, as `_attend`'s
_PREFILL_TILE_MAX = 896  # no tile is wider
_PREFILL_VMEM = 16 << 20  # what `prefill_tile`'s sum may reach


def _prefill_vmem(t: int, dh: int, group: int, itemsize: int,
                  *, own_keys: bool = False, rope: int = 0) -> int:
    """VMEM of one grid step at tile t, in bytes (`prefill_tile`,
    ops/latent_attention.py `latent_prefill_block`). `own_keys`: each of
    the step's `group` heads has key and value lanes of its own, so those
    tiles are as wide as the queries'; `rope`: the lanes of a rotary part
    beside each head's query (as fetched, and once more turned), of the one
    rotary key tile the heads share and of the two float32 tables."""
    qo = 2 * 2 * t * group * dh * itemsize  # query and context tiles, double-buffered
    kv = 2 * 2 * t * (group if own_keys else 1) * dh * itemsize  # key and value tiles, likewise
    acc = t * group * dh * 4  # float32 accumulator
    ml = 2 * group * t * 128 * 4  # running maximum and sum, lane-replicated
    live = 3 * t * t * 4  # one head's scores, their exponentials, the mask
    # each head's rotary lanes double-buffered and once more turned, the one
    # rotary key double-buffered, two float32 tables double-buffered
    turned = t * rope * ((3 * group + 2) * itemsize + 2 * 2 * 4)
    return qo + kv + acc + ml + live + turned


def prefill_tile(p: int, dh: int, group: int, itemsize: int = 2) -> int:
    """The tile of `prefill_attention` and of ops/sparse_attention.py
    `sparse_prefill_attention`, queries and keys alike, for a width p that
    128 divides: the largest multiple of 128 that divides p, is at most
    896 and keeps the VMEM sum of a grid step (`_prefill_vmem`) under
    16 MB. A function of the shapes alone: 640 at rag-cerebras-6b7's
    (p 1280, a group of 1, bf16: 7.2 MB), 512 at
    rag-smallthinker-21b-a3b's (p 10,240, 7 query heads a key head:
    12.8 MB; 640 would be 17.0), 256 at rag-minicpm-sala's (p 24,576, 16
    query heads a key head). 128 always divides, and is the tile of a
    group so large that no tile fits: the kernel's VMEM limit follows the
    sum.
    A larger tile amortises the grid step's fixed cost (about 0.35 us),
    the key tile's fetch and the MXU's weight loads; a smaller one wastes
    less on the diagonal and the band's edges. On the chip (PERF.md
    section 6, PR 39) the tile body runs at the rate of its two products
    and a tile's time is its elements' at any shape of one area: at
    p 10,240 x 7 heads 5.26 ms at 512 x 512 and 5.12 to 5.63 at 384 x 512,
    1024 x 256, 512 x 1024, 256 x 1024 and 640 x 640 (queries x keys),
    6.97 at 256 x 256; at p 24,576 x 16 heads 33.4 ms at 256 x 256 and
    31.1 to 34.4 at 512 x 256, 512 x 512 and 256 x 1024: under a hundredth
    of a prefill either way, so the tile stays one number. The softmax
    made a few rows at a time to stay in registers was 1.3-10 times
    slower than the whole tile's (PR 32). A kernel whose heads share no
    key tile chooses how many of them a grid step runs as well:
    ops/latent_attention.py `latent_prefill_block` has that rule and its
    table (PR 44: a step of one head ran at half the rate a group of
    seven has here)."""
    fits = [
        t for t in range(128, min(p, _PREFILL_TILE_MAX) + 1, 128)
        if p % t == 0 and _prefill_vmem(t, dh, group, itemsize) <= _PREFILL_VMEM
    ]
    return fits[-1] if fits else 128


def _first_key_tile(qi, first, t: int, window: int | None):
    """The first key tile that query tile `qi` needs: past the tiles that
    hold no valid key before the first that does and, in a window layer,
    those wholly left of the band of its first row."""
    if window is None:
        return first
    return jnp.maximum(first, jax.lax.div(jnp.maximum(qi * t - window + 1, 0), t))


def _along_lanes(x, width: int):
    """A lane-replicated [rows, 128] array at `width` lanes, as it lies:
    whole vregs named again, where a slice of lane 0 broadcast back
    (`x[:, :1]`) is a permute through the XLU for every row group."""
    return x if width == x.shape[1] else jnp.tile(x, (1, width // x.shape[1]))


def _fold_key_tile(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, ok,
                   *, group: int, dh: int, scale: float, dv: int | None = None,
                   own_keys: bool = False, rope=None):
    """The tile body of the streaming softmax, `prefill_attention`'s,
    ops/sparse_attention.py `sparse_prefill_attention`'s and
    ops/latent_attention.py `latent_prefill_attention`'s: the query tile
    [t, group * dh] of the grid step's heads against one key tile, a head at
    a time, into the head's running maximum and sum ([t, 128], every lane
    the same) and its float32 accumulator. The key tile is [t, dh], rows of
    one key head that the group's query heads share (the first two
    callers), or with `own_keys` [group * dh, t]: each head's own dh rows
    of a tile whose lanes are the positions (the third, whose heads share
    no key: its group is a block of heads, and its keys and values lie as
    their products leave them, heads outermost). The value tile likewise.
    `ok` [t, t] says which pairs are allowed, None that all are.
    `dv`: the values' width (and the accumulator's, a head) where it is not
    the keys'. `rope`: (a query tile [t, group * lanes], one key tile
    [t, lanes]) of a second part of every head's score, a product of its
    own against a key all heads share, added in float32 before the scale.

    The loop is unrolled, so the compiler's scheduler sees the step's heads
    as one stretch of straight-line code and runs one head's softmax behind
    another's products; a grid step of one head has nothing beside it
    (PERF.md section 6, PR 39 and PR 44).
    The running maximum and the rescale meet the scores and the accumulator
    as the lane-replicated arrays they are kept as (`_along_lanes`). That
    leaves the XLU to the two row reductions, and the softmax hides behind
    the two products: on a v5e a global layer of 10,240 tokens and 28 heads
    over 4 takes 5.3 ms so and took 10.7 with the slices, softmax and
    products in turn (PERF.md section 6, PR 39, which also has what was
    tried on top and dropped: the scale inside `exp2`, the row sum on the
    MXU, the next head's product issued first)."""
    if not own_keys:
        k, v = k_ref[0], v_ref[0]
    dv = dv or dh
    # the axis of a key tile that holds a head's lanes, and of a value tile
    # the positions: rows of keys, or keys along the lanes
    k_lanes, v_rows = (0, 1) if own_keys else (1, 0)
    for g in range(group):
        lanes, out = slice(g * dh, (g + 1) * dh), slice(g * dv, (g + 1) * dv)
        if own_keys:
            k, v = k_ref[0, lanes, :], v_ref[0, out, :]
        s = jax.lax.dot_general(
            q_ref[0, :, lanes], k, (((1,), (k_lanes,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if rope is not None:
            qr_ref, kr_ref = rope
            dr = kr_ref.shape[-1]
            s = s + jax.lax.dot_general(
                qr_ref[0, :, g * dr:(g + 1) * dr], kr_ref[0],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            )
        s = s * scale  # [t, t]
        if ok is not None:
            s = jnp.where(ok, s, _MASKED)
        m_prev = m_ref[g]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a row none of whose allowed keys has come yet keeps its maximum
        # at the mask's value and sums exponentials of 0; its first allowed
        # key moves the maximum and alpha wipes them
        e = jnp.exp(s - _along_lanes(m_new, s.shape[1]))
        l_ref[g] = alpha * l_ref[g] + jnp.sum(e, axis=1, keepdims=True)
        m_ref[g] = m_new
        acc_ref[:, out] = acc_ref[:, out] * _along_lanes(alpha, dv) + jax.lax.dot_general(
            e.astype(v.dtype), v, (((1,), (v_rows,)), ((), ())),
            preferred_element_type=jnp.float32,
        )


def _start_softmax(m_ref, l_ref, acc_ref):
    """A query tile's first grid step: nothing seen yet."""
    m_ref[...] = jnp.full(m_ref.shape, _MASKED, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)


def _finish_softmax(o_ref, l_ref, acc_ref, *, group: int, dh: int):
    """A query tile's last grid step: the accumulators over the sums."""
    for g in range(group):
        lanes = slice(g * dh, (g + 1) * dh)
        total = _along_lanes(l_ref[g], dh)
        # a row of a query tile that ran no key tile (all of it padding)
        # has summed nothing: it returns zeros
        o_ref[0, :, lanes] = (
            acc_ref[:, lanes] / jnp.where(total == 0.0, 1.0, total)
        ).astype(o_ref.dtype)


def _prefill_kernel(first_ref, held_ref, q_ref, k_ref, v_ref, valid_ref, o_ref,
                    m_ref, l_ref, acc_ref,
                    *, t: int, group: int, dh: int, window: int | None,
                    scale: float):
    """One grid step (row, key head, query tile, k-th needed key tile): the
    group's query tile [t, group * dh] against one key tile [t, dh]."""
    bi, qi, kk = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    kt = _first_key_tile(qi, first_ref[bi], t, window) + kk  # the key tile
    held = held_ref[bi * pl.num_programs(2) + jnp.minimum(kt, qi)]  # its valid keys
    q0, k0 = qi * t, kt * t

    @pl.when(kk == 0)
    def _start():
        _start_softmax(m_ref, l_ref, acc_ref)

    fold = functools.partial(
        _fold_key_tile, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
        group=group, dh=dh, scale=scale,
    )
    # a tile on the diagonal, on the band's edge or with a key that is not
    # valid takes an element mask; an inner tile takes none
    edge = (kt == qi) | (held < t)
    if window is not None:
        edge = edge | (k0 <= q0 + t - 1 - window)
    # past the diagonal the clamped tile is not run again; a tile with no
    # valid key is not run at all
    needed = (kt <= qi) & (held > 0)

    @pl.when(needed & edge)
    def _edge():
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
        ok = (kpos <= qpos) & (valid_ref[0] != 0)
        if window is not None:
            ok = ok & (kpos > qpos - window)
        fold(ok)

    @pl.when(needed & jnp.logical_not(edge))
    def _inner():
        fold(None)

    @pl.when(kk == pl.num_programs(3) - 1)
    def _finish():
        _finish_softmax(o_ref, l_ref, acc_ref, group=group, dh=dh)


# jitted so that the layers of one program share one trace of the kernel
# and one lowering to Mosaic for each window they have: traced and lowered
# a layer at a time, the kernel adds seconds to every load of a prefill
# program, also one fetched from the persistent compile cache
@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def prefill_attention(
    q: jax.Array,  # [b, p, heads, dh]
    k: jax.Array,  # [b, p, kv heads, dh]
    v: jax.Array,  # [b, p, kv heads, dh]
    valid: jax.Array,  # [b, p] 1/0: the keys that are real
    window: int | None = None,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Causal attention of whole prompts to themselves, the scores kept in
    VMEM: query i of a row attends the valid keys j <= i and, in a window
    layer, j > i - window. Returns the context [b, p, heads * dh]. Products
    in the inputs' dtype with float32 accumulation, softmax in float32, as
    models/layers.py `attend` states them; the order of the sums is
    another. A query with no key to attend (a row of the padding) returns
    some finite vector.

    The inputs are read as `_qkv` leaves them: a block of dh lanes of the
    flattened head axis is one head, so the grid's key-head index picks the
    key head and the `heads / kv heads` query heads that share it, which
    read one key tile. dh must be a multiple of 128 (a lane tile). A width
    that is no multiple of 128 (of the prompt-length ladder only the cap
    `max_len - n_steps` is none) is padded at the end to the next one:
    keys never valid, queries cut off.

    Grid (b, kv heads, p / t, key tiles a query tile can need), t from
    `prefill_tile`. Key tile kk of query tile qi is tile
    `_first_key_tile(qi) + kk`, clamped to the diagonal's: a grid step
    past the diagonal names the tile already in VMEM, so it fetches nothing
    and runs nothing. A window layer's last grid axis is as long as its
    band, not as the prompt. The batcher left-pads a prompt to its rung:
    the key tiles wholly inside that padding are neither fetched nor run."""
    b, p0, h, dh = q.shape
    hk = k.shape[2]
    group = h // hk
    if dh % 128 or h % hk:
        raise ValueError(f"prefill_attention needs heads of a multiple of 128 "
                         f"lanes in whole groups, got {h} x {dh} over {hk}")
    extra = -p0 % 128
    if extra:
        q, k, v = (jnp.pad(a, ((0, 0), (0, extra), (0, 0), (0, 0))) for a in (q, k, v))
        valid = jnp.pad(valid, ((0, 0), (0, extra)))
    p = p0 + extra
    t = prefill_tile(p, dh, group, q.dtype.itemsize)
    n = p // t
    n_keys = n if window is None else min(n, -(-(window - 1) // t) + 1)
    valid = valid.astype(jnp.int32)
    held = jnp.sum(valid.reshape(b, n, t), axis=2)  # valid keys of each key tile
    first = jnp.argmax(held > 0, axis=1).astype(jnp.int32)

    def q_block(bi, j, qi, kk, first_ref, held_ref):
        return bi, qi, j

    def key_tile(bi, qi, kk, first_ref):
        return jnp.minimum(_first_key_tile(qi, first_ref[bi], t, window) + kk, qi)

    def k_block(bi, j, qi, kk, first_ref, held_ref):
        return bi, key_tile(bi, qi, kk, first_ref), j

    def valid_block(bi, j, qi, kk, first_ref, held_ref):
        return bi, 0, key_tile(bi, qi, kk, first_ref)

    out = pl.pallas_call(
        functools.partial(
            _prefill_kernel, t=t, group=group, dh=dh, window=window,
            scale=1.0 / math.sqrt(dh),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hk, n, n_keys),
            in_specs=[
                pl.BlockSpec((1, t, group * dh), q_block),
                pl.BlockSpec((1, t, dh), k_block),
                pl.BlockSpec((1, t, dh), k_block),
                pl.BlockSpec((1, 1, t), valid_block),
            ],
            out_specs=pl.BlockSpec((1, t, group * dh), q_block),
            scratch_shapes=[
                pltpu.VMEM((group, t, 128), jnp.float32),  # running maximum
                pltpu.VMEM((group, t, 128), jnp.float32),  # running sum
                pltpu.VMEM((t, group * dh), jnp.float32),  # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, p, h * dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            # the sum, and as much again for what the compiler adds
            vmem_limit_bytes=2 * max(
                _PREFILL_VMEM, _prefill_vmem(t, dh, group, q.dtype.itemsize)
            ),
        ),
        name="prefill_attention",
        interpret=interpret,
    )(
        first, held.reshape(b * n),
        q.reshape(b, p, h * dh), k.reshape(b, p, hk * dh), v.reshape(b, p, hk * dh),
        valid.reshape(b, 1, p),
    )
    return out[:, :p0] if extra else out


# ------------------------------------------------- decode-step attention
#
# A step's query is one token a slot, so its attention is a read of the
# slot cache and little else. Written in XLA it reads every row the cache
# has room for, and copies a layer's rows out of the stacked leaf first
# where the heads are grouped (PERF.md section 5). The kernel below takes
# the stacked leaf as it lies, fetches the tiles that hold a live row, and
# writes the step's own row into the leaf on its way.

_DECODE_BLOCK = 1 << 20  # bytes of keys (and of values) a grid step fetches


def decode_block(rows: int, kv_heads: int, dh: int, itemsize: int = 2
                 ) -> tuple[int, int]:
    """The block of `decode_attention`: (key heads, rows) a grid step
    fetches of a leaf that keeps `rows` rows a slot and head. A function
    of the shapes alone: about a megabyte of keys, so that the step's
    fixed cost (about 0.35 us) is small beside the fetch, made of as many
    heads and as few rows as that allows, because rows are what the tile
    of the last live row wastes: (32, 128) at rag-cerebras-6b7's (32 key
    heads of 128, 2,048 rows), (4, 1024) at rag-smallthinker-21b-a3b's
    (4 key heads; 16,384 rows and the ring's 4,096 alike). On the chip
    (PERF.md, PR 34) these fetch at 85% and 82-84% of the HBM bandwidth;
    (16, 256), (8, 512) and (4, 512), (4, 2048) were no faster."""
    t = min(rows, 128)
    hb = kv_heads
    while hb > 1 and (kv_heads % hb or hb * t * dh * itemsize > _DECODE_BLOCK):
        hb -= 1
    if hb == kv_heads:
        while 2 * t <= rows and 2 * hb * t * dh * itemsize <= _DECODE_BLOCK:
            t *= 2
    return hb, t


def _written_rows(t: int, itemsize: int) -> int:
    """Rows of the block the step's own row is written back in: the rows
    that share a packed sublane tile (16 of bfloat16, 8 of float32), or
    the whole tile where those do not divide it."""
    packed = 8 * max(1, 4 // itemsize)
    return packed if t % packed == 0 else t


def _decode_tiles(pos, pad, t: int, rows: int):
    """The first and the last tile of `t` rows that hold a live row of a
    slot at physical position `pos` behind a left pad of `pad`: rows
    pad .. pos until the ring has wrapped, every tile after."""
    wrapped = pos >= rows
    last = jax.lax.div(jnp.where(wrapped, rows - 1, pos), t)
    first = jnp.where(wrapped, 0, jnp.minimum(jax.lax.div(pad, t), last))
    return first, last


def _decode_kernel(layer_ref, pos_ref, pad_ref, q_ref, kn_ref, vn_ref, k_ref,
                   v_ref, o_ref, ko_ref, vo_ref, m_ref, l_ref, acc_ref,
                   *, t: int, sub: int, rows: int, scale: float):
    """One grid step (slot, block of key heads, kk-th needed key tile): the
    slot's query heads [hb, group, dh] against a tile [hb, t, dh] of the
    keys and values of the key heads they share."""
    del layer_ref  # the index maps' business
    si, kk = pl.program_id(0), pl.program_id(2)
    pos, pad = pos_ref[si], pad_ref[si]
    first, last = _decode_tiles(pos, pad, t, rows)
    kt = first + kk
    turn = jax.lax.rem(pos, rows)  # the row of the step's own key

    @pl.when(kk == 0)
    def _start():
        # the step's own key is the first the softmax meets: it comes
        # from the operands, for it is in no tile of the leaf yet
        own = jnp.sum(
            q_ref[...].astype(jnp.float32) * kn_ref[...].astype(jnp.float32),
            axis=2, keepdims=True,
        ) * scale  # [hb, group, 1]
        m_ref[...] = jnp.broadcast_to(own, m_ref.shape)
        l_ref[...] = jnp.ones(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.broadcast_to(
            vn_ref[...].astype(jnp.float32), acc_ref.shape
        )

    # past the last live tile the clamped tile is not run again
    @pl.when(kt <= last)
    def _fold():
        k, v = k_ref[...], v_ref[...]
        s = jax.lax.dot_general(
            q_ref[...], k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale  # [hb, group, t]
        # row j of the ring holds the newest position <= pos that is j
        # modulo its length (`_step_rows`' own rule, without a vector
        # remainder): a key unless that lies before the pad. The step's
        # own row still holds what it held a ring ago
        row = kt * t + jax.lax.broadcasted_iota(jnp.int32, (1, 1, t), 2)
        held = pos - turn + row - jnp.where(row > turn, rows, 0)
        ok = (held >= pad) & (row != turn)
        if rows % t:
            # the last tile hangs over the leaf's end: what was fetched
            # from there is no key, and anything: 0 x NaN is no 0
            ok = ok & (row < rows)
            inside = kt * t + jax.lax.broadcasted_iota(
                jnp.int32, (1, t, 1), 1
            ) < rows
            v = jnp.where(inside, v, jnp.zeros_like(v))
        s = jnp.where(ok, s, _MASKED)
        m_prev = m_ref[...]  # [hb, group, 128], every lane the same
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        e = jnp.exp(s - m_new[:, :, :1])
        l_ref[...] = alpha * l_ref[...] + jnp.sum(e, axis=2, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha[:, :, :1] + jax.lax.dot_general(
            e.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )

    # the tile of the step's own row is always among the live ones: the
    # few rows around it go back to the leaf with the new row among them
    @pl.when(kt == jax.lax.div(turn, t))
    def _write():
        at = jax.lax.div(turn - kt * t, sub) * sub
        mine = kt * t + at + jax.lax.broadcasted_iota(
            jnp.int32, (1, sub, 1), 1
        ) == turn
        for new_ref, old_ref, out_ref in (
            (kn_ref, k_ref, ko_ref), (vn_ref, v_ref, vo_ref)
        ):
            old = old_ref[:, pl.ds(pl.multiple_of(at, sub), sub), :]
            out_ref[...] = jnp.where(mine, new_ref[...], old)

    @pl.when(kk == pl.num_programs(2) - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / l_ref[...][:, :, :1]).astype(o_ref.dtype)


# jitted for `prefill_attention`'s reason; the layer is an operand, not a
# constant of the kernel, so that one lowering serves every layer of a leaf
@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention(
    q: jax.Array,  # [slots, heads, dh]: one token a slot
    k_new: jax.Array,  # [slots, kv heads, dh]: the token's key
    v_new: jax.Array,  # [slots, kv heads, dh]: and value
    k_cache: jax.Array,  # [layers, slots, kv heads, rows, dh]: a whole leaf
    v_cache: jax.Array,  # the same
    layer: jax.Array,  # scalar int32: the layer's index along the leaf
    pos: jax.Array,  # [slots] int32: each slot's physical position
    pad_len: jax.Array,  # [slots] int32: each slot's left pad
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One step of a layer's attention over the slot cache, read and
    written where it lies: each slot's key and value go into row
    `pos mod rows` of its slot, and its query attends the slot's live rows,
    that one among them. The leaves are operands and results of one
    buffer: the layer and the slot are picked by the index maps, nothing
    is copied out of a leaf and nothing but the new rows goes in. Returns
    (context [slots, heads * dh], k_cache, v_cache).

    A leaf's rows are a ring of their own number: row j holds the newest
    position <= pos that is j modulo `rows`, and is a key iff that
    position is not before `pad_len`. A global layer's leaf has `max_len`
    rows and never wraps, so its keys are rows pad_len .. pos; a window
    layer's has W. Until a ring has wrapped only the tiles
    pad_len // t .. pos // t are fetched: a grid step past the last names
    the tile already in VMEM, so it fetches nothing and runs nothing (as
    `prefill_attention` past the diagonal). A free slot (pos 0) writes and
    attends its row 0.

    Products in the cache's dtype with float32 accumulation, softmax in
    float32, the weights cast to the cache's dtype before the second
    product, as models/layers.py `attend` states them; the order of
    the sums is another. The `heads / kv heads` query heads of a group
    share each key tile. dh must be a multiple of 128 (a lane tile).
    Grid (slots, kv heads / hb, tiles of t rows), (hb, t) from
    `decode_block`."""
    n, h, dh = q.shape
    _, _, hk, rows, _ = k_cache.shape
    group = h // hk
    if dh % 128 or h % hk:
        raise ValueError(f"decode_attention needs heads of a multiple of 128 "
                         f"lanes in whole groups, got {h} x {dh} over {hk}")
    hb, t = decode_block(rows, hk, dh, k_cache.dtype.itemsize)
    sub = _written_rows(t, k_cache.dtype.itemsize)

    def q_block(si, hi, kk, layer_ref, pos_ref, pad_ref):
        return si, hi, 0, 0

    def k_block(si, hi, kk, layer_ref, pos_ref, pad_ref):
        first, last = _decode_tiles(pos_ref[si], pad_ref[si], t, rows)
        return layer_ref[0], si, hi, jnp.minimum(first + kk, last), 0

    def written_block(si, hi, kk, layer_ref, pos_ref, pad_ref):
        return layer_ref[0], si, hi, jax.lax.div(jax.lax.rem(pos_ref[si], rows), sub), 0

    leaf = jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype)
    out, k_cache, v_cache = pl.pallas_call(
        functools.partial(
            _decode_kernel, t=t, sub=sub, rows=rows, scale=1.0 / math.sqrt(dh)
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n, hk // hb, pl.cdiv(rows, t)),
            in_specs=[
                pl.BlockSpec((None, hb, group, dh), q_block),
                pl.BlockSpec((None, hb, 1, dh), q_block),
                pl.BlockSpec((None, hb, 1, dh), q_block),
                pl.BlockSpec((None, None, hb, t, dh), k_block),
                pl.BlockSpec((None, None, hb, t, dh), k_block),
            ],
            out_specs=[
                pl.BlockSpec((None, hb, group, dh), q_block),
                pl.BlockSpec((None, None, hb, sub, dh), written_block),
                pl.BlockSpec((None, None, hb, sub, dh), written_block),
            ],
            scratch_shapes=[
                pltpu.VMEM((hb, group, 128), jnp.float32),  # running maximum
                pltpu.VMEM((hb, group, 128), jnp.float32),  # running sum
                pltpu.VMEM((hb, group, dh), jnp.float32),  # accumulator
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((n, hk, group, dh), q.dtype), leaf, leaf],
        # the leaves are written in place (operands count the three
        # prefetched scalars too)
        input_output_aliases={6: 1, 7: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="decode_attention",
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), pos.astype(jnp.int32),
        pad_len.astype(jnp.int32), q.reshape(n, hk, group, dh),
        k_new.reshape(n, hk, 1, dh), v_new.reshape(n, hk, 1, dh),
        k_cache, v_cache,
    )
    return out.reshape(n, h * dh), k_cache, v_cache


# --------------------------------------------------------- ring attention
#
# Long-context sequence/context parallelism: the sequence is sharded
# across a mesh axis; K/V blocks rotate around the ring via ppermute
# while each device accumulates its queries' attention with a streaming
# (flash-style) softmax. Peak memory per device is O(s_local^2) scores
# and one K/V block — sequences scale with the ring size. Communication
# rides ICI (ppermute neighbors), overlapping with each step's matmuls
# under XLA's latency-hiding scheduler.
#
# Reference parity: replaces the single-device torch SDPA ceiling of the
# reference's local models with the standard ring-attention construction
# (blockwise-parallel transformers over a device ring).


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = False,
    kv_mask: jax.Array | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Exact multi-head attention over a sequence sharded on `axis_name`.

    Call INSIDE shard_map with q/k/v [b, s_local, h, dh] holding this
    device's sequence block (global sequence = blocks in axis order).
    `kv_mask` [b, s_local] marks valid key positions of the local block
    (it rotates around the ring with K/V). Returns ctx [b, s_local, h, dh].
    """
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    b, s_loc, h, dh = q.shape
    sc = scale if scale is not None else 1.0 / math.sqrt(dh)
    qf = q.astype(jnp.float32)
    q_pos = my * s_loc + jnp.arange(s_loc)

    def accumulate(o, m, l, kblk, vblk, mblk, i):
        """Fold the currently-held K/V block into the streaming softmax."""
        src = (my - i) % n  # block index currently held
        scores = (
            jnp.einsum("bqhd,bkhd->bhqk", qf, kblk.astype(jnp.float32)) * sc
        )
        valid = mblk[:, None, None, :].astype(bool)
        if causal:
            k_pos = src * s_loc + jnp.arange(s_loc)
            valid = valid & (q_pos[:, None] >= k_pos[None, :])[None, None, :, :]
        scores = jnp.where(valid, scores, -jnp.inf)
        blk_max = jnp.max(scores, axis=-1)  # [b,h,q]
        new_m = jnp.maximum(m, blk_max)
        # rows with no valid key anywhere so far keep m=-inf; exp(-inf-(-inf))
        # would be NaN — pin those rows to 0 contribution
        safe_m = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        p = jnp.where(
            jnp.isfinite(scores), jnp.exp(scores - safe_m[..., None]), 0.0
        )
        l = l * corr + jnp.sum(p, axis=-1)
        o = o * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vblk.astype(jnp.float32)
        )
        return o, new_m, l

    ring = [(j, (j + 1) % n) for j in range(n)]

    def step(carry, i):
        # rotate first, then accumulate: n-1 rotations total (the local
        # block is folded before the scan; a final-step rotation would
        # only be discarded)
        o, m, l, kblk, vblk, mblk = carry
        kblk = jax.lax.ppermute(kblk, axis_name, ring)
        vblk = jax.lax.ppermute(vblk, axis_name, ring)
        mblk = jax.lax.ppermute(mblk, axis_name, ring)
        o, m, l = accumulate(o, m, l, kblk, vblk, mblk, i)
        return (o, m, l, kblk, vblk, mblk), None

    # build the initial carries FROM q so they inherit q's varying-axes
    # set under shard_map (the scan carry types must match whatever axes
    # the body's outputs vary over — ring axis AND any batch axes)
    o0 = jnp.transpose(qf * 0.0, (0, 2, 1, 3))  # [b,h,s,dh] zeros
    l0 = o0[..., 0]  # [b,h,s] zeros
    m0 = l0 - jnp.inf  # [b,h,s] -inf
    mask0 = (
        kv_mask if kv_mask is not None else jnp.ones((b, s_loc), jnp.int32)
    )
    o0, m0, l0 = accumulate(o0, m0, l0, k, v, mask0, 0)  # local block
    if n > 1:
        (o, m, l, _k, _v, _m), _ = jax.lax.scan(
            step, (o0, m0, l0, k, v, mask0), jnp.arange(1, n)
        )
    else:
        o, l = o0, l0
    out = o / jnp.maximum(l[..., None], 1e-30)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)
