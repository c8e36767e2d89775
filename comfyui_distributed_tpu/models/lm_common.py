"""What the eleven language models share (`deepseek_v2.py`, `ouro.py`,
`solar_open2.py`, `k_exaone.py`, `ling_flash.py`, `nemotron_h.py`, `glm_dsa.py`,
`granite_hybrid.py`, `sdar.py`, `dots3.py`, `longcat_flash.py`): the blocks and
helpers they are written from, the one initialisation rule, sampling on the
device, the rule by which a drafted token is kept or replaced and the
rule by which a block's drawn tokens are kept or masked again, the three
decode loops, the prefill in parts, and the stand-in tokenizer.

A bundle's `lm` part is an object with this contract (`LanguageModel`
below holds what every model's class has alike), which is all that
`graph/nodes_text.TextGenerate` knows of a model:

- `cfg`, `tokenizer`, `init(key, dtype)` (which also records `dtype`,
  the one the weights and the cache are stored in);
- `prefill(params, ids, cache_len, collect)` and `decode(params, cache,
  logits, start, key, steps, temperature, collect, draft_tokens)`: the
  two programs; what they return has `.cache` and `.logits` (the
  prefill's) and `.ids` (the decode's: `[steps]`, always). `.cache` is a
  request's whole state, one array or a tree of them, handed from one
  program to the other as it is; the node never looks inside;
- `draft_tokens_max`: tokens a decode step may draft and verify beside
  the one it emits anyway (0: no draft module, and `decode` refuses any
  other `draft_tokens`);
- `read_back(prefill, decode)`: the device arrays a request reads back
  beside the ids, in the one `device.wait`;
- `report(prompt_tokens, new_tokens, cache_len, *read)`: everything the
  model says on `node.TextGenerate`: what its own shapes and dtypes give
  (`layers`; `cache_bytes`, what grows with `cache_len`; `state_bytes`,
  what does not), and what `read_back`'s arrays do as the host got them.
  The first part is also callable alone, `describe(cache_len)`: the
  benchmark's reader tests hold their counts by hand against it;
- `layer_passes`, the layer bodies one token walks through, from which
  `counted` answers what the node's counters take where `report` does
  not say otherwise (`decode_steps`, `prefill_layer_passes`,
  `decode_layer_passes`: a model whose step runs more than one position).

What a model's two programs are written from, so that a loop is written
once:

- a `decode` is the model's one-token step handed to `decode_loop`
  (every model that emits its tokens in order);
- where the model has an MTP module, `draft_tokens` 1 is its `mtp_step`
  and `main_step` behind one return shape, and what it does to its own
  state once a draft's fate is known, handed to `draft_loop` (K-EXAONE,
  Ling-3.0-flash, GLM-5.2), which owns the keys, the draft, `verify`,
  the ids and the first three of `counts`; `drafts` is the refusal of
  any other number and `drafting_report` the steps' half of `report`;
- a `prefill` that cannot take its prompt at once hands one part's body
  and the arrays it wants cut to `prefill_in_parts` (GLM-5.2,
  granite-4.0-h-micro, dots3-note-prev, LongCat-Flash: what a part hands on is a
  tree of whatever the model needs, caches of full length, recurrent
  states, the last latents of a window), which owns the cut (`parts_of`), the scan over
  the whole parts, the remainder and the joining of the parts' outputs;
  the model allocates the state before it and reads the outputs after;
- where the model generates by masked diffusion over blocks, a `decode`
  is its pass over one block of positions handed to `denoise_loop`
  (SDAR), which owns the blocks' ids and masks, the keys, the draws
  (`sample_with_confidence`), the rule (`transfer`), the closing pass
  and `counts`: a step's yield is a set of positions, not a suffix.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import route_log


# --- blocks ---------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (normed * scale.astype(jnp.float32)).astype(x.dtype)


def clamped_silu_product(gate: jax.Array, up: jax.Array, limit: float = 0.0) -> jax.Array:
    """silu(gate) * up, a SwiGLU's middle; under a `limit` > 0 the gate
    is held to at most it and up to within it either way first (0: no
    clamp)."""
    if limit:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return jax.nn.silu(gate) * up


def swiglu(x: jax.Array, p: dict, limit: float = 0.0) -> jax.Array:
    gate, up = jnp.split(x @ p["w_gate_up"], 2, axis=-1)
    return clamped_silu_product(gate, up, limit) @ p["w_down"]


def relu2_mlp(x: jax.Array, p: dict) -> jax.Array:
    """An ungated feed-forward part, two matrices: relu(x W_up)^2 W_down."""
    return jnp.square(jax.nn.relu(x @ p["w_up"])) @ p["w_down"]


def short_conv(projected: jax.Array, filters: jax.Array, tail: jax.Array):
    """A causal depth-wise convolution over the token axis, float32:
    `projected` [T, channels], `filters` [kernel, channels], `tail`
    [kernel - 1, channels] the inputs of the tokens before. Returns
    (the sums [T, channels] float32, before any bias or activation, and
    the inputs themselves, tail first, [kernel - 1 + T, channels]: the
    rows after token t's are the tail token t leaves). What a KDA layer
    runs over its q, k and v projections (`kda.conv_qkv`) and a Mamba-2
    layer over x, B and C (`mamba2.mixer_inputs`)."""
    tokens, kernel = projected.shape[0], filters.shape[0]
    window = jnp.concatenate([tail, projected.astype(tail.dtype)], axis=0)
    filters = filters.astype(jnp.float32)
    mixed = sum(window[i:i + tokens].astype(jnp.float32) * filters[i] for i in range(kernel))
    return mixed, window


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate [..., T, (heads,) rope] by its position, the two halves of
    the last axis as the pair's members (`rotate_half`)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    if x.ndim == cos.ndim + 1:  # a heads axis between T and rope
        cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def apply_rope_pairs(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate [..., T, (heads,) rope] by its position, channels 2i and
    2i + 1 as the pair's members (the interleaved form, as a checkpoint
    stores it)."""
    x1, x2 = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    if x.ndim == cos.ndim + 1:  # a heads axis between T and rope
        cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.stack(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).reshape(x.shape).astype(x.dtype)


def rope_tables(theta: float, head_dim: int, positions: jax.Array):
    """cos and sin, [T, head_dim / 2] float32, of the positions' angles at
    the frequencies theta^(-2i / head_dim); no scaling."""
    inv_freq = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    angles = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)
    return jnp.cos(angles), jnp.sin(angles)


def head(cfg, params, h, norm=None):
    """Float32 logits of h [T, hidden]: a norm (the final one unless
    another scale is given), then the output embedding."""
    with jax.named_scope("head"):
        h = rms_norm(h, params["final_norm"] if norm is None else norm, cfg.rms_norm_eps)
        return jnp.dot(h, params["head"], preferred_element_type=jnp.float32)


def sample(logits, key, temperature):
    """The next id from float32 logits: the largest at temperature 0,
    else a draw from softmax(logits / temperature). `temperature` is a
    traced scalar, so every value runs the one program."""
    drawn = jax.random.categorical(key, logits / jnp.where(temperature > 0, temperature, 1.0))
    return jnp.where(temperature > 0, drawn, jnp.argmax(logits)).astype(jnp.int32)


def sample_with_confidence(logits, key, temperature):
    """`sample`, and beside the id the probability softmax(logits /
    temperature) gives it (at temperature 0: the largest logit's id and
    its probability at temperature 1): what a masked-diffusion step's
    rule (`transfer`) ranks the drawn ids by."""
    drawn = sample(logits, key, temperature)
    scaled = logits / jnp.where(temperature > 0, temperature, 1.0)
    return drawn, jnp.exp(scaled[drawn] - jax.nn.logsumexp(scaled))


# --- unmasking: which of a block's drawn ids are kept ----------------------


def transfer(confidence, masked, n, threshold):
    """The dynamic low-confidence rule over one block: `confidence` [B]
    float32 of the ids drawn at each position, `masked` [B] which
    positions are still masked, `n` how many a pass has to fill in at
    least, `threshold` the confidence above which a drawn id is kept
    anyway. If the masked positions above the threshold number `n` or
    more, all of them are kept; else the `n` most confident masked ones,
    ties to the lower index. A position that is not masked is never
    kept (fewer than `n` may be left). Returns (kept [B] bool, whether
    the threshold decided)."""
    confidence = jnp.where(masked, confidence, -jnp.inf)
    above = confidence > threshold
    index = jnp.arange(confidence.shape[0])
    ahead = (confidence[None, :] > confidence[:, None]) | (
        (confidence[None, :] == confidence[:, None]) & (index[None, :] < index[:, None]))
    by_threshold = above.sum() >= n
    return jnp.where(by_threshold, above, (ahead.sum(axis=1) < n) & masked), by_threshold


def blocks_most(steps: int, block: int) -> int:
    """The blocks a `denoise_loop` of `steps` new ids may walk: one more
    than the ids fill where the first opens with left-over prompt tokens."""
    return -(-(steps + block - 1) // block)


def denoise_loop(block_step, cache, opening, start, key, temperature, steps: int, block: int,
                 passes: int, threshold: float, mask_id: int, kept_stride: int = 1):
    """`steps` new ids by masked diffusion over blocks of `block`
    positions: a `fori_loop` over the blocks from the one that holds
    position `start` (the prompt's length; the `start` % `block` prompt
    tokens past the last whole block are `opening`'s first entries and
    open the first block unmasked) and inside it a `while_loop` of
    denoising passes that ends with the closing pass. A block starts as
    its known ids and `mask_id` elsewhere. A denoising pass runs the
    block through the model, draws an id at every masked position
    (`sample_with_confidence`, the key folded by block, then pass, then
    split a position) and `transfer` keeps at least `block` // `passes`
    of them (one more in the first `block` % `passes` passes), so no
    block takes more than `passes`; once no position is masked the
    closing pass runs the block's final ids through the model, whose
    writes to its state are the ones that stand. What is masked is a
    boolean of the loop's own, not read off the ids.

    What a model hands over: `block_step(cache, tokens [block],
    position, close)` -> (logits [block, vocab] float32 (a closing pass:
    None, nothing reads them), cache, a tree the loop sums over the
    passes, a dict of which the loop keeps every denoising pass's or
    None), the shape `decode_loop`'s `step` has; a logit at row i is of
    position `position` + i itself. `close` is static: two traces.

    Returns (cache, ids [steps]: the first `steps` after `start`, counts
    [4] int32: denoising passes, closing passes, positions kept by the
    threshold, by the floor; the summed tree; and where a pass keeps
    anything, rows [blocks the loop may walk, `passes`, ...]: the loop's
    own `tokens` (the block as the pass saw it), `masked`, `drawn`,
    `moved`, `position` (-1 where no pass was taken) for every pass, and
    the step's own dict for the passes of every `kept_stride`-th block
    [ceil(blocks / stride), `passes`, ...]; else None)."""
    most = blocks_most(steps, block)
    left = start % block
    first = start - left
    index = jnp.arange(block)

    def denoise(b, s, cache, tokens, masked):
        position = first + b * block
        logits, cache, added, now = block_step(cache, tokens, position, False)
        with jax.named_scope("transfer"):
            keys = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, b), s), block)
            drawn, confidence = jax.vmap(sample_with_confidence, (0, 0, None))(
                logits, keys, temperature)
            n = block // passes + (s < block % passes)
            moved, by_threshold = transfer(confidence, masked, n, threshold)
        own = {"tokens": tokens, "masked": masked, "drawn": drawn, "moved": moved,
               "position": position}
        counted = jnp.stack([1, 0, 0, 0]) + moved.sum() * jnp.stack(
            [0, 0, by_threshold, ~by_threshold])
        return (cache, jnp.where(moved, drawn, tokens), masked & ~moved,
                counted.astype(jnp.int32), added, own, now)

    def one_block(b, carry):
        cache, ids, counts, tally, kept = carry
        opens = (b == 0) & (index < left)
        tokens = jnp.where(opens, opening, mask_id).astype(jnp.int32)

        def body(c):
            s, cache, tokens, masked, counts, tally, kept = c
            cache, tokens, masked, counted, added, own, now = denoise(b, s, cache, tokens, masked)
            if kept is not None:
                mine, theirs = kept
                mine = jax.tree_util.tree_map(lambda rows, row: rows.at[b, s].set(row), mine, own)
                row_b = jnp.where(b % kept_stride == 0, b // kept_stride, most)
                theirs = jax.tree_util.tree_map(
                    lambda rows, row: rows.at[row_b, s].set(row, mode="drop"), theirs, now)
                kept = (mine, theirs)
            return (s + 1, cache, tokens, masked, counts + counted,
                    jax.tree_util.tree_map(jnp.add, tally, added), kept)

        _, cache, tokens, _, counts, tally, kept = jax.lax.while_loop(
            lambda c: c[3].any(), body,
            (jnp.int32(0), cache, tokens, ~opens, counts, tally, kept))
        with jax.named_scope("close"):
            _, cache, added, _ = block_step(cache, tokens, first + b * block, True)
        ids = jax.lax.dynamic_update_slice(ids, tokens, (b * block,))
        return (cache, ids, counts.at[1].add(1),
                jax.tree_util.tree_map(jnp.add, tally, added), kept)

    # what a pass adds and keeps, as shapes: see `decode_loop`
    with route_log():
        *_, tally, own, kept = jax.eval_shape(
            denoise, jnp.int32(0), jnp.int32(0), cache, jnp.zeros((block,), jnp.int32),
            jnp.ones((block,), bool))
    if kept is not None:
        rows = -(-most // kept_stride)
        kept = (
            {**zeros(jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct((most, passes, *s.shape), s.dtype), own)),
             "position": jnp.full((most, passes), -1, jnp.int32)},
            zeros(jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct((rows, passes, *s.shape), s.dtype), kept)))
    carry = (cache, jnp.zeros((most * block,), jnp.int32), jnp.zeros((4,), jnp.int32),
             zeros(tally), kept)
    cache, ids, counts, tally, kept = jax.lax.fori_loop(
        0, (left + steps + block - 1) // block, one_block, carry)
    if kept is not None:
        kept = {**kept[0], **kept[1]}
    return cache, jax.lax.dynamic_slice(ids, (left,), (steps,)), counts, tally, kept


# --- drafting: the lossless rule of a self-speculative step ----------------


def mtp_input(cfg, params, h, tokens):
    """What a multi-token-prediction module's layer takes (DeepSeek-V3's
    form, which the three drafting models' modules share): u [T,
    hidden] = W_eh [rms_e(E[x_{i+1}]) ; rms_h(h_i)] of the residual
    streams h [T, hidden] after the last main layer and the tokens that
    follow each [T]; `params["mtp"]` holds `embed_norm`, `hidden_norm`
    and `w_eh`."""
    p = params["mtp"]
    both = jnp.concatenate([
        rms_norm(params["embed"][tokens], p["embed_norm"], cfg.rms_norm_eps),
        rms_norm(h, p["hidden_norm"], cfg.rms_norm_eps),
    ], axis=-1)
    return both @ p["w_eh"]


def accept_probability(p, q, draft):
    """With which probability a draft drawn from q stands for a draw
    from p: min(1, p(draft) / q(draft))."""
    return jnp.minimum(1.0, p[draft] / q[draft])


def residual(p, q):
    """What a rejected draft is replaced from: max(p - q, 0) over its
    sum (p itself where the two are equal and nothing is left)."""
    left = jnp.maximum(p - q, 0.0)
    total = left.sum()
    return jnp.where(total > 0, left / jnp.where(total > 0, total, 1.0), p)


def verify(logits, draft_logits, draft, key, temperature):
    """The lossless rule over the main model's logits [2, vocab] at the
    last emitted token and at the draft, and the logits the draft was
    drawn from. Returns (kept, the token after the last emitted one, the
    token after that, which counts only where the draft was kept). At
    temperature 0 the draft is kept iff it is the main model's largest."""
    key_accept, key_again, key_next = jax.random.split(key, 3)
    safe = jnp.where(temperature > 0, temperature, 1.0)
    p = jax.nn.softmax(logits[0] / safe)
    q = jax.nn.softmax(draft_logits / safe)
    kept = jnp.where(
        temperature > 0,
        jax.random.uniform(key_accept) < accept_probability(p, q, draft),
        draft == jnp.argmax(logits[0]))
    again = jnp.where(
        temperature > 0,
        jax.random.categorical(key_again, jnp.log(residual(p, q))),
        jnp.argmax(logits[0])).astype(jnp.int32)
    return kept, jnp.where(kept, draft, again), sample(logits[1], key_next, temperature)


# --- a request's state and the decode loop --------------------------------


def nbytes(shape: jax.ShapeDtypeStruct) -> int:
    return math.prod(shape.shape) * jnp.dtype(shape.dtype).itemsize


def zeros(shapes):
    """A `ShapeDtypeStruct`, or a dict or tuple of them, as arrays of
    zeros, made in the order they are written in."""
    if isinstance(shapes, jax.ShapeDtypeStruct):
        return jnp.zeros(shapes.shape, shapes.dtype)
    if isinstance(shapes, dict):
        return {name: zeros(s) for name, s in shapes.items()}
    return tuple(zeros(s) for s in shapes)


def decode_loop(step, cache, logits, start, key, temperature, steps: int):
    """`steps` dependent one-token steps as one `fori_loop`, from the
    prefill's `logits` at position `start - 1`: draw id i from the logits
    with the key folded by i, run it through `step(cache, token, start +
    i)`, which returns (logits, cache, a tree the loop sums over the
    steps, a tree of which the loop keeps every step's or None). Always
    `steps` ids, no early stop. Returns (cache, ids [steps], the summed
    tree, the kept rows stacked or None)."""

    def body(i, carry):
        cache, logits, ids, tally, kept = carry
        token = sample(logits, jax.random.fold_in(key, i), temperature)
        logits, cache, tally_i, kept_i = step(cache, token, start + i)
        if kept is not None:
            kept = jax.tree_util.tree_map(lambda rows, row: rows.at[i].set(row), kept, kept_i)
        return (cache, logits, ids.at[i].set(token),
                jax.tree_util.tree_map(jnp.add, tally, tally_i), kept)

    # what a step adds and keeps, as shapes: the step's own word. Nothing is run, and
    # the routes this extra tracing logs are dropped: the loop's body logs the program's
    with route_log():
        _, _, tally, kept = jax.eval_shape(
            step, cache, jax.ShapeDtypeStruct((), jnp.int32), start)
    if kept is not None:
        kept = zeros(jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((steps, *s.shape), s.dtype), kept))
    carry = (cache, logits, jnp.zeros((steps,), jnp.int32), zeros(tally), kept)
    cache, _, ids, tally, kept = jax.lax.fori_loop(0, steps, body, carry)
    return cache, ids, tally, kept


def drafts(draft_tokens: int) -> bool:
    """Whether a decode drafts, of a model whose MTP module drafts one
    token a step; any other number is refused."""
    if draft_tokens not in (0, 1):
        raise ValueError(f"this model's MTP module drafts one token a step, not {draft_tokens}")
    return bool(draft_tokens)


def draft_loop(mtp_step, main_step, cache, logits, start, key, temperature, steps: int,
               settle=lambda cache, accepted: cache):
    """`steps` ids as one `while_loop` of self-speculative steps, from the
    prefill's `logits` at position `start - 1`, of which id 0 is drawn
    (the key folded by 0). A step (its keys: the key folded by its index +
    1, split in two) drafts one token with the MTP module, runs the last
    emitted token and the draft through the main model as two positions
    and `verify` keeps the draft or replaces it, so it emits one id or two
    and the loop takes as many steps as the drafts' fates make it. Before
    a step the main model's state holds positions 0 .. n - 1, x_n is the
    last emitted token, and `waiting` of the newest confirmed positions
    (their residual streams `h`, the tokens that follow them `after`) have
    not been through the module yet: one after a rejection, two after a
    kept draft; before the first it is `cache["h"]`, the prompt's last.

    What a model hands over: `mtp_step(cache, h [2, hidden], after [2],
    position)` and `main_step(cache, tokens [2], position)`, which return
    alike (logits [2, vocab], the residual streams [2, hidden] (the
    module's: None), cache, a tree the loop sums over the steps, a dict
    of which the loop keeps every step's or None), and
    `settle(cache, accepted)`: what it does to its own state once a
    draft's fate is known (nothing, unless it says otherwise). The draft
    is drawn from the module's row `waiting` - 1, the row kept of what the
    module keeps; the other is of no confirmed position where one waits,
    and what it writes at n the next step writes over.

    Returns (cache, ids [steps], counts [3] int32: steps taken, drafts
    made, drafts kept, (the module's summed tree, the main model's), and
    where the main step keeps anything the kept rows stacked, a row a
    step the loop may take, else None: the steps' own and `logits` [2,
    vocab] (row 0 position n's, row 1 the draft's at n + 1),
    `draft_logits`, `position` (n; -1 where no step was taken) and
    `accepted`)."""
    first = sample(logits, jax.random.fold_in(key, 0), temperature)

    def advance(c):
        cache, emitted = c["cache"], c["emitted"]
        n = start + emitted - 1  # x_n's position
        key_draft, key_verify = jax.random.split(jax.random.fold_in(key, c["counts"][0] + 1))
        with jax.named_scope("mtp"):
            rows_mtp, _, cache, added_mtp, now_mtp = mtp_step(
                cache, c["h"], c["after"], n - c["waiting"])
            draft_logits = rows_mtp[c["waiting"] - 1]
            draft = sample(draft_logits, key_draft, temperature)
        rows, h, cache, added, now = main_step(cache, jnp.stack([c["last"], draft]), n)
        with jax.named_scope("verify"):
            accepted, one, two = verify(rows, draft_logits, draft, key_verify, temperature)
            ids = c["ids"].at[emitted].set(one)
            # a second token that would be one too many is not written
            ids = ids.at[jnp.where(accepted, emitted + 1, steps)].set(two, mode="drop")
            counts = c["counts"] + jnp.stack([1, 1, accepted]).astype(jnp.int32)
        if now is not None:
            now = {
                **now, "logits": rows, "draft_logits": draft_logits, "position": n,
                "accepted": accepted,
                **jax.tree_util.tree_map(lambda a: a[c["waiting"] - 1], now_mtp or {}),
            }
        return {
            "cache": settle(cache, accepted), "ids": ids, "emitted": emitted + 1 + accepted,
            "last": jnp.where(accepted, two, one), "h": h, "after": jnp.stack([one, two]),
            "waiting": 1 + accepted.astype(jnp.int32), "counts": counts,
        }, (added_mtp, added), now

    def body(carry):
        c, tally, kept = carry
        step = c["counts"][0]
        c, added, now = advance(c)
        return (c, jax.tree_util.tree_map(jnp.add, tally, added),
                jax.tree_util.tree_map(lambda rows, row: rows.at[step].set(row), kept, now))

    most = max(steps - 1, 1)  # steps the loop may take: each emits at least one token
    c = {
        "cache": cache, "ids": jnp.zeros((steps,), jnp.int32).at[0].set(first),
        "emitted": jnp.int32(1), "last": first,
        "h": jnp.stack([cache["h"], jnp.zeros_like(cache["h"])]),
        "after": jnp.stack([first, jnp.int32(0)]), "waiting": jnp.int32(1),
        "counts": jnp.zeros((3,), jnp.int32),
    }
    # what a step adds and keeps, as shapes: see `decode_loop`
    with route_log():
        _, tally, kept = jax.eval_shape(advance, c)
    if kept is not None:
        kept = zeros(jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((most, *s.shape), s.dtype), kept))
        kept["position"] = jnp.full((most,), -1, jnp.int32)
    c, tally, kept = jax.lax.while_loop(
        lambda carry: carry[0]["emitted"] < steps, body, (c, zeros(tally), kept))
    return c["cache"], c["ids"], c["counts"], tally, kept


def drafting_report(counts, k: int, sparse_layers: int, layers: int) -> tuple[int, dict]:
    """What a decode's `counts` [4] (steps taken, drafts made, drafts
    kept, held experts read) come to on `node.TextGenerate`, of a model
    of `layers` main layers, `sparse_layers` of them sparse with `k`
    experts a token: (the positions a step ran, the attributes). The
    pairs and the layer bodies are counted over every position a step
    ran, a rejected draft's and the MTP module's among them."""
    steps, drafted, accepted, read = (int(n) for n in counts)
    width = 2 if drafted else 1  # positions a step runs
    mtp = 1 if drafted else 0    # and whether the module's layer is among its bodies
    pairs = steps * width * k * (sparse_layers + mtp)
    return width, {
        "decode_routed_pairs": pairs, "decode_expert_rows": pairs,
        "decode_steps": steps, "mtp_drafted": drafted, "mtp_accepted": accepted,
        "decode_layer_passes": steps * width * (layers + mtp),
        "decode_experts_read": read,
    }


def parts_of(tokens: int, part: int) -> tuple[int, int]:
    """(whole parts of `part` positions, positions left over)."""
    return divmod(tokens, part)


def prefill_in_parts(body, state, arrays: tuple, part: int):
    """A prompt read in parts of `part` positions (`parts_of`), each over
    the state the parts before left: the whole parts one `lax.scan` body,
    what is left over a body of its own. `arrays` are what the model
    wants cut, each [T], an entry a position; `body(state, cuts, start,
    ends) -> (state, out)` takes a part's entries of them, the position
    it starts at (traced) and, statically, where the parts this body
    serves may end (`start` // `part` is this one's place among them).
    `out` has one shape whatever the part's length. Returns (state, the
    parts' outs in order, joined along a leading parts axis)."""
    tokens = arrays[0].shape[0]
    whole, left = parts_of(tokens, part)
    outs = []
    if whole:
        ends = tuple(part * (i + 1) for i in range(whole))
        state, out = jax.lax.scan(
            lambda state, xs: body(state, xs[:-1], xs[-1], ends), state,
            (*(a[:whole * part].reshape(whole, part) for a in arrays), jnp.arange(whole) * part))
        outs.append(out)
    if left:
        state, out = body(
            state, tuple(a[tokens - left:] for a in arrays), jnp.int32(tokens - left), (tokens,))
        outs.append(jax.tree_util.tree_map(lambda a: a[None], out))
    return state, jax.tree_util.tree_map(lambda *a: jnp.concatenate(a), *outs)


# --- parameters -----------------------------------------------------------
# A tree of specs: each leaf ((shape), fan_in), fan_in None for a norm's
# scale, initialised to one.


def mlp_shapes(hidden: int, width: int) -> dict:
    """One SwiGLU's specs: gate and up side by side, then down."""
    return {"w_gate_up": ((hidden, 2 * width), hidden), "w_down": ((width, hidden), width)}


def is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def count_params(shapes: dict[str, Any]) -> int:
    specs = jax.tree_util.tree_leaves(shapes, is_leaf=is_spec)
    return sum(math.prod(shape) for shape, _ in specs)


@partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _normal(key, shape, std, dtype):
    if len(shape) >= 3 and math.prod(shape) >= 2**28:
        # a stack of experts or of layers (or a run's stack of stacks),
        # one at a time: the float32 draw of a whole stack (2.5 GB at
        # the published widths) is never alive at once
        keys = jax.random.split(key, shape[0])
        return jax.lax.map(
            lambda k: (jax.random.normal(k, shape[1:], jnp.float32) * std).astype(dtype), keys
        )
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_from_shapes(shapes: dict[str, Any], key, dtype=jnp.float32) -> dict[str, Any]:
    """Seeded random weights built in `dtype`, weight by weight: normal
    with standard deviation fan_in^-1/2, so activations keep their scale
    through the depth (and a router's logits spread by about one)."""
    specs, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=is_spec)
    dtype = jnp.dtype(dtype)
    leaves = []
    for index, (shape, fan_in) in enumerate(specs):
        if fan_in is None:
            leaves.append(jnp.ones(shape, dtype))
        else:
            leaves.append(
                _normal(jax.random.fold_in(key, index), shape, float(fan_in) ** -0.5, dtype)
            )
    return jax.tree_util.tree_unflatten(treedef, leaves)


# --- the stand-in tokenizer -----------------------------------------------


class ByteTokenizer:
    """A stand-in for the published tokenizers, which are not in the
    sandbox: deterministic and byte-level, into the first ids of the
    vocabulary (or of its slice). `encode`: id 0 (begin of sentence), then
    1 + b for each byte b of the text's UTF-8. `decode`: ids 1..256 give
    their byte where it is printable ASCII and nothing otherwise; id 0
    gives nothing; every other id n gives a space and then n - 257 written
    in base 26 with the letters a..z, least digit first. So any ids come
    back as lower-case words that CLIP's BPE can tokenise."""

    BOS = 0
    BYTES = 256

    def encode(self, text: str) -> list[int]:
        return [self.BOS] + [1 + b for b in text.encode("utf-8")]

    def decode(self, ids) -> str:
        pieces = []
        for n in map(int, ids):
            if n == self.BOS:
                continue
            if n <= self.BYTES:
                pieces.append(chr(n - 1) if 32 <= n - 1 < 127 else "")
                continue
            n -= self.BYTES + 1
            word = chr(97 + n % 26)
            while n >= 26:
                n //= 26
                word += chr(97 + n % 26)
            pieces.append(" " + word)
        return "".join(pieces).strip()


# --- what a model's class starts from --------------------------------------


class LanguageModel:
    """The part of the contract that is the same for every model: the
    configuration with the module's three functions bound to it
    (`init_params(cfg, key, dtype)`, the jitted `prefill(cfg, params, ids,
    *, cache_len, collect)` and `decode(cfg, params, cache, logits, start,
    key, temperature, *, steps, collect)`, which a subclass names as
    `_init`, `_prefill`, `_decode`), the tokenizer, the dtype the weights
    and the cache are stored in, and `counted`. A subclass adds
    `layer_passes`, `read_back` and `report` (over its `describe`), and
    where it has a draft module `draft_tokens_max` and a `_decode` that
    takes `draft_tokens`."""

    _init = _prefill = _decode = None
    draft_tokens_max = 0

    def __init__(self, cfg):
        self.cfg = cfg
        self.tokenizer = ByteTokenizer()
        self.dtype = jnp.dtype(jnp.float32)  # until `init` says otherwise

    def init(self, key, dtype=jnp.float32):
        self.dtype = jnp.dtype(dtype)
        return self._init(self.cfg, key, dtype)

    def counted(self, report: dict, prompt_tokens: int, new_tokens: int) -> dict[str, int]:
        """What the node's counters take of a request: the model's own
        `report` where it says them, else a step a token and each token
        through `layer_passes` layer bodies."""
        defaults = {
            "decode_steps": new_tokens,
            "prefill_layer_passes": prompt_tokens * self.layer_passes,
            "decode_layer_passes": new_tokens * self.layer_passes,
        }
        return {key: report.get(key, value) for key, value in defaults.items()}

    def prefill(self, params, ids, cache_len: int, collect: bool = False):
        return self._prefill(self.cfg, params, ids, cache_len=cache_len, collect=collect)

    def decode(self, params, cache, logits, start: int, key, steps: int, temperature: float,
               collect: bool = False, draft_tokens: int = 0):
        if not 0 <= draft_tokens <= self.draft_tokens_max:
            raise ValueError(
                f"draft_tokens {draft_tokens}: {type(self).__name__} "
                + (f"drafts at most {self.draft_tokens_max} a step" if self.draft_tokens_max
                   else "has no draft module; only 0 (one token a step) is served"))
        drafting = {"draft_tokens": draft_tokens} if self.draft_tokens_max else {}
        return self._decode(
            self.cfg, params, cache, logits, jnp.int32(start), key, jnp.float32(temperature),
            steps=steps, collect=collect, **drafting,
        )
