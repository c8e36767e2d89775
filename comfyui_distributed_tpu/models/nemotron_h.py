"""Nemotron-H (NVIDIA-Nemotron-3-Nano-30B-A3B): a stack of blocks that
are each one part alone. The published `hybrid_override_pattern` says
what block l is, `M` a Mamba-2 state-space mixer, `E` a mixture of
experts, `*` softmax attention, and every block is

    h += part(rms(h))

under one norm scale a block; no block pairs a mixer with a feed-forward
part. A final RMS norm, an untied head, the residual stream in the
storage dtype.

`M` is `models/mamba2.py` whole (the two forms, the convolution tail,
the gated group norm). `*`: q = W_q x [heads, d], k, v = W_k x, W_v x
[key heads, d] (32 : 2 as published: 16 queries a key head), scores
q . k / sqrt(d) under the causal mask, softmax, W_o; no bias, no gate, no
QK norm and **no rotary embedding**: position comes from the Mamba
blocks around them. `E`: `moe.expert_layer` under `moe.sigmoid_route`
(sigmoid scores, a selection bias, the `num_experts_per_tok` largest,
the chosen scores renormalised, times `routed_scaling_factor`; `n_group`
1: no group step), its experts without a gate: relu(x W_up)^2 W_down,
and one shared expert of the same form that every token takes.

**The tree**: `params["blocks"]` has an entry a published block, a tree
of its own weights, and a block that keeps a state has a leaf of its
own in the request's state. **The walk** (`walk`) is chosen by what it
sees, the token count. One token (a decode step) goes through the 52
blocks one after another: 52 bodies whose matrices are whole arrays,
read once where they lie, and whose states are read and overwritten
where they lie in the loop's carry; the Mamba and the sparse block are
jitted there, so that the step traces and lowers each once and calls it
23 times. Until PR 49 a maximal run of `EM` pairs lay stacked along a
leading axis under one `lax.scan` in both programs: the scan handed a
run's states back as a new stack, which the decode's loop copied into
its carry every step (46 MB), and a step that walked the pairs itself,
reading each one's weights out of the stacks by a static index, had
whole stacks prefetched and waited for (PERF.md section 6, PR 49). A
sequence (the prefill) still takes a run under one `lax.scan`
(`scanned_run`), over the pairs' weights stacked inside the program
once a request: with 52 prefill bodies, 23 of them holding the expert
layer's ladder of row counts, every cached start spent 22 s fetching
the two compiled programs, where it spends 10 now and spent 6 with the
runs scanned in both (PERF.md section 6, PR 49).

`plan` cuts the pattern into segments, a maximal run of two or more
`EM` pairs one segment and every other block its own (the published
string: one `M`, then runs of 2, 3, 3, 3, 3, 4, 4 pairs with an
attention block before each but the first, then one `E`): what the
prefill scans, and how the weights are **drawn**, a run's pairs as one
stack in one program and then handed out block by block, so that a
seed's weights are what they were when the runs were stacks.

A request's state (`state_shapes`) has an entry only for what keeps
one: `kv`, one `[2, key heads, positions, d]` array an attention block,
which grows; `ssm` and `conv`, one `[H, P, N]` float32 state and one
`[kernel - 1, inner + 2 G N]` tail a Mamba block, which do not. The
prefill allocates it, the decode takes it by donation and hands it
back.

The chip holds `expert_range(ep_rank, ep_size)` of the experts and the
first of `vocab_shards` slices of the vocabulary, as `DeepSeekV2Config`
has it.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..ops.attention import dot_product_attention
from ..ops.decode_attention import attend_xla, position_valid
from ..parallel.sharding import expert_range
from . import mamba2
from .lm_common import (
    LanguageModel,
    count_params,
    decode_loop,
    head,
    init_from_shapes,
    is_spec,
    nbytes,
    rms_norm,
    zeros,
)
from .moe import decode_route, expert_layer, prefill_route, report_loads, sigmoid_route

PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """The published `config.json`'s shape keys under their own names,
    and the chip's share of a deployment as `DeepSeekV2Config` states
    it."""

    hidden_size: int = 2688
    hybrid_override_pattern: str = PUBLISHED_PATTERN
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    n_routed_experts: int = 128
    n_shared_experts: int = 1
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    num_experts_per_tok: int = 6
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    vocab_size: int = 131072
    layer_norm_epsilon: float = 1e-5
    ep_size: int = 1
    ep_rank: int = 0
    vocab_shards: int = 1

    def __post_init__(self):
        unknown = sorted(set(self.hybrid_override_pattern) - set("ME*"))
        if unknown:
            raise ValueError(f"a block is M, E or *, not {unknown}")

    @property
    def num_hidden_layers(self) -> int:
        return len(self.hybrid_override_pattern)

    @property
    def rms_norm_eps(self) -> float:  # what `lm_common.head` reads
        return self.layer_norm_epsilon

    @property
    def held_experts(self) -> range:
        return expert_range(self.n_routed_experts, self.ep_rank, self.ep_size)

    @property
    def vocab_held(self) -> int:
        return self.vocab_size // self.vocab_shards

    def blocks_of(self, kind: str) -> list[int]:
        """The published indices of the blocks of one kind."""
        return [i for i, k in enumerate(self.hybrid_override_pattern) if k == kind]

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size


class Segment(NamedTuple):
    kind: str    # "M", "E", "*": one block; "EM": a run of pairs (one stack drawn, one scan)
    first: int   # the published index of its first block
    pairs: int   # pairs in a run, else 0

    @property
    def blocks(self) -> int:
        return 2 * self.pairs or 1


def plan(pattern: str) -> tuple[Segment, ...]:
    """The pattern cut into segments in order: a maximal run of two or
    more `EM` pairs is one, every other block its own."""
    segments, at = [], 0
    while at < len(pattern):
        pairs = 0
        while pattern[at + 2 * pairs:at + 2 * pairs + 2] == "EM":
            pairs += 1
        if pairs >= 2:
            segments.append(Segment("EM", at, pairs))
            at += 2 * pairs
        else:
            segments.append(Segment(pattern[at], at, 0))
            at += 1
    return tuple(segments)


# --- parameters -----------------------------------------------------------

_PART = {"M": "mamba", "E": "moe", "*": "attn"}


def _block_shapes(cfg: NemotronHConfig, kind: str, pairs: int = 0) -> dict[str, Any]:
    """One block's specs, or those of `pairs` such blocks stacked along
    a leading axis (a run's)."""
    h = cfg.hidden_size
    if kind == "M":
        part = mamba2.shapes(h, cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                             cfg.ssm_state_size, cfg.conv_kernel)
    elif kind == "*":
        heads, kv = cfg.num_attention_heads * cfg.head_dim, cfg.num_key_value_heads * cfg.head_dim
        part = {"w_q": ((h, heads), h), "w_k": ((h, kv), h), "w_v": ((h, kv), h),
                "w_o": ((heads, h), heads)}
    else:
        held, width = len(cfg.held_experts), cfg.moe_intermediate_size
        shared = cfg.moe_shared_expert_intermediate_size * cfg.n_shared_experts
        part = {
            "w_g": ((h, cfg.n_routed_experts), h),
            "bias": ((cfg.n_routed_experts,), None),
            "experts": {"w_up": ((held, width, h), h), "w_down": ((held, width, h), width)},
            "shared": {"w_up": ((h, shared), h), "w_down": ((shared, h), shared)},
        }
    block = {"norm": ((h,), None), _PART[kind]: part}
    if not pairs:
        return block
    return jax.tree_util.tree_map(
        lambda spec: ((pairs, *spec[0]), spec[1]), block, is_leaf=is_spec)


def _segment_shapes(cfg: NemotronHConfig, segment: Segment) -> dict[str, Any]:
    if segment.pairs:
        return {"e": _block_shapes(cfg, "E", segment.pairs),
                "m": _block_shapes(cfg, "M", segment.pairs)}
    return _block_shapes(cfg, segment.kind)


def param_shapes(cfg: NemotronHConfig) -> dict[str, Any]:
    """The tree's shapes with each weight's fan-in (None: a norm's
    scale, initialised to one); `blocks` has an entry a published
    block."""
    return {
        "embed": ((cfg.vocab_held, cfg.hidden_size), 1),
        "blocks": tuple(_block_shapes(cfg, kind) for kind in cfg.hybrid_override_pattern),
        "final_norm": ((cfg.hidden_size,), None),
        "head": ((cfg.hidden_size, cfg.vocab_held), cfg.hidden_size),
    }


def param_count(cfg: NemotronHConfig) -> int:
    return count_params(param_shapes(cfg))


@partial(jax.jit, static_argnames=("cfg", "segment", "dtype"))
def _init_segment(key, *, cfg: NemotronHConfig, segment: Segment, dtype):
    """One segment's blocks' weights in one program, a tuple of a tree a
    block: a run's pairs are drawn as one stack along a leading axis
    (the values a seed gave while the runs were stacks) and handed out
    pair by pair. `segment.first` is 0 here whatever its place: the
    segments of one kind and length are one program, and the published
    string has 15 segments of 6 kinds, where its 130 weights drawn one a
    program cost a cold start 130 compiles (156 s of a 168 s load on a
    v5e; PERF.md section 6, PR 48)."""
    params = init_from_shapes(_segment_shapes(cfg, segment), key, dtype)
    key = jax.random.fold_in(key, 1)
    for half in (params.values() if segment.pairs else (params,)):
        if "moe" in half:
            half["moe"]["bias"] = jnp.zeros_like(half["moe"]["bias"], jnp.float32)
        if "mamba" in half:
            half["mamba"].update(mamba2.init_steps(
                key, half["mamba"]["a_log"].shape, cfg.time_step_min, cfg.time_step_max,
                cfg.time_step_floor))
    if not segment.pairs:
        return (params,)
    return tuple(
        jax.tree_util.tree_map(lambda leaf: leaf[pair], params[half])
        for pair in range(segment.pairs) for half in "em")


def init_params(cfg: NemotronHConfig, key, dtype=jnp.float32) -> dict[str, Any]:
    """Seeded random weights in `dtype` (`lm_common.init_from_shapes`), a
    segment of `plan` a program under a key of its own; the router's selection
    bias zero; a Mamba block's `a_log`, `dt_bias` and `d` by the layer's
    published initialisation (`mamba2.init_steps`), float32 whatever
    `dtype`: no bias is shifted, the published ranges already give a
    state that holds from one to a thousand tokens."""
    ends = {name: spec for name, spec in param_shapes(cfg).items() if name != "blocks"}
    dtype = jnp.dtype(dtype)
    return {
        **init_from_shapes(ends, jax.random.fold_in(key, len(cfg.hybrid_override_pattern)), dtype),
        "blocks": tuple(
            block for segment in plan(cfg.hybrid_override_pattern)
            for block in _init_segment(jax.random.fold_in(key, segment.first), cfg=cfg,
                                       segment=segment._replace(first=0), dtype=dtype)),
    }


def unstacked(cfg: NemotronHConfig, params: dict) -> dict:
    """The tree with `blocks` as a sequence of the published blocks:
    the tree as it is held. The name dates from when a run's pairs lay
    stacked; the reference's callers (`benchmark/nemotron3_nano_parity.py`
    among them) ask by it."""
    return params


# --- a request's state ----------------------------------------------------


def state_shapes(cfg: NemotronHConfig, cache_len: int, dtype) -> dict[str, tuple]:
    """The tree a request carries from its prefill through its decode:
    `kv` an entry an attention block, `ssm` and `conv` an entry a Mamba
    block, in published order. A leaf a block, never a stack of several:
    the decode's loop carries the tree, and a leaf is updated where it
    lies."""
    mamba_blocks = len(cfg.blocks_of("M"))
    matrix = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size)
    kv = (2, cfg.num_key_value_heads, cache_len, cfg.head_dim)
    tail = (cfg.conv_kernel - 1, cfg.conv_channels)
    return {
        "kv": (jax.ShapeDtypeStruct(kv, dtype),) * len(cfg.blocks_of("*")),
        "ssm": (jax.ShapeDtypeStruct(matrix, jnp.float32),) * mamba_blocks,
        "conv": (jax.ShapeDtypeStruct(tail, dtype),) * mamba_blocks,
    }


# --- the three parts --------------------------------------------------------


def route(cfg: NemotronHConfig, bias: jax.Array, logits: jax.Array):
    """`moe.sigmoid_route` at this model's sizes: (ids, weights)."""
    return sigmoid_route(
        logits, bias, cfg.num_experts_per_tok, cfg.routed_scaling_factor, cfg.norm_topk_prob,
        cfg.n_group, cfg.topk_group)


def moe(cfg, p, x, index=None):
    """(output, chosen ids [T, k], pairs on each held expert [held]);
    with `index`, `p["experts"]` is a run's stack and this block's
    experts are that entry of it."""
    return expert_layer(p, x, cfg.held_experts, partial(route, cfg, p["bias"]), index=index)


def mamba(cfg, p, x, tail, state):
    """(output, tail, state): `mamba2.mixer` at this model's sizes."""
    return mamba2.mixer(
        p, x, tail, state, cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
        cfg.ssm_state_size, cfg.chunk_size, cfg.layer_norm_epsilon)


def _qkv(cfg, p, x):
    tokens = x.shape[0]
    q = (x @ p["w_q"]).reshape(tokens, cfg.num_attention_heads, cfg.head_dim)
    k = (x @ p["w_k"]).reshape(tokens, cfg.num_key_value_heads, cfg.head_dim)
    v = (x @ p["w_v"]).reshape(tokens, cfg.num_key_value_heads, cfg.head_dim)
    return q, k, v


def attn_whole(cfg, p, x, kv):
    """Over a whole sequence x [T, hidden] (the prefill's form): its keys
    and values written into the first T positions of `kv`. Returns
    (output [T, hidden], kv)."""
    q, k, v = _qkv(cfg, p, x)
    out = dot_product_attention(q[None], k[None], v[None], causal=True)[0]
    kv = jax.lax.dynamic_update_slice(kv, jnp.stack([k, v]).transpose(0, 2, 1, 3), (0, 0, 0, 0))
    return out.reshape(x.shape[0], -1) @ p["w_o"], kv


def attn_cached(cfg, position, p, x, kv):
    """One new token x [1, hidden] at `position`: its key and value
    written into `kv` [2, key heads, positions, d], attention over the
    positions up to it (`decode_attention.attend_xla`, a key head
    serving its 16 queries). Returns (output [1, hidden], kv)."""
    q, k, v = _qkv(cfg, p, x)
    kv = jax.lax.dynamic_update_slice(
        # one token: [2, 1, heads, d] and [2, heads, 1, d] are the same bytes
        kv, jnp.stack([k, v]).reshape(2, cfg.num_key_value_heads, 1, cfg.head_dim),
        (0, 0, position, 0))
    valid = position_valid(jnp.asarray(position).reshape(1), kv.shape[2])
    out = attend_xla(q, kv[None], (0,), valid)
    return out.reshape(1, -1) @ p["w_o"], kv


# --- the walk -------------------------------------------------------------


def mamba_block(cfg: NemotronHConfig, block: dict, h, tail, state):
    """h += mamba(rms(h)) over the block's tail and state: (h, tail, state)."""
    with jax.named_scope("mamba"):
        out, tail, state = mamba(
            cfg, block["mamba"], rms_norm(h, block["norm"], cfg.layer_norm_epsilon), tail, state)
    return h + out, tail, state


def sparse_block(cfg: NemotronHConfig, block: dict, h, index=None):
    """h += moe(rms(h)): (h, chosen ids [T, k], pairs on each held expert
    [held]); `index` as `moe` takes it."""
    out, ids, sizes = moe(
        cfg, block["moe"], rms_norm(h, block["norm"], cfg.layer_norm_epsilon), index)
    return h + out, ids, sizes


def scanned_run(cfg: NemotronHConfig, blocks: tuple, h, tails: list, states: list):
    """A run of `EM` pairs over a sequence (the prefill), under one
    `lax.scan` over the pairs' weights, tails and states, stacked here
    once a request (6.1 GB copied, 17.6 ms of a 0.47 s program): walked
    block by block a prefill is 52 bodies for 16 and 15 ms shorter, but
    its compiled program costs every cached start 12 s more to fetch
    (PERF.md section 6, PR 49). The routed experts' stacks stay whole
    beside the scan, a pair's read by its index where they lie: what
    the scan slices out it copies. Returns (h, tails, states, ids
    [pairs, T, k], sizes [pairs, held])."""
    stacked = lambda trees: jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *trees)
    sparse, mixers = stacked(blocks[0::2]), stacked(blocks[1::2])
    experts = sparse["moe"].pop("experts")

    def pair(h, xs):
        index, e, m, tail, state = xs
        h, ids, sizes = sparse_block(cfg, {**e, "moe": {**e["moe"], "experts": experts}}, h, index)
        h, tail, state = mamba_block(cfg, m, h, tail, state)
        return h, (tail, state, ids, sizes)

    h, (tails, states, ids, sizes) = jax.lax.scan(
        pair, h, (jnp.arange(len(states)), sparse, mixers, jnp.stack(tails), jnp.stack(states)))
    return h, list(tails), list(states), ids, sizes


def walk(cfg: NemotronHConfig, blocks: tuple, h, cache: dict, attn):
    """h [T, hidden] through the 52 blocks over the request's state:
    `attn(p, x, kv) -> (output, kv)` is the attention block's form (the
    other two parts have one form each way). One token (a decode step)
    goes block after block, each matrix a whole array read where it lies
    and each state updated where it lies in the loop's carry; a sequence
    takes a run of pairs through `scanned_run`. Returns (h, cache, the
    experts chosen [E blocks, T, k], the pairs on each held expert [E
    blocks, held]), the E blocks in published order."""
    kv, ssm, conv = (list(cache[name]) for name in ("kv", "ssm", "conv"))
    chosen, loads, kv_at, ssm_at = [], [], 0, 0
    # jitted here and not at the module's level: a program traces and lowers each of
    # the two kinds that come 23 times once, and a new trace of the program (the
    # parity script's, under a patched `mamba2.mixer`) traces them anew
    one_mamba, one_sparse = (
        jax.jit(partial(block, cfg)) for block in (mamba_block, sparse_block))
    for segment in plan(cfg.hybrid_override_pattern):
        mine = blocks[segment.first:segment.first + segment.blocks]
        if segment.pairs and h.shape[0] > 1:
            held = slice(ssm_at, ssm_at + segment.pairs)
            with jax.named_scope(f"run_{segment.first}_{segment.first + segment.blocks - 1}"):
                h, conv[held], ssm[held], ids, sizes = scanned_run(
                    cfg, mine, h, conv[held], ssm[held])
            ssm_at += segment.pairs
            chosen.extend(ids)
            loads.extend(sizes)
            continue
        for index, block in enumerate(mine, segment.first):
            with jax.named_scope(f"block_{index}"):
                if "mamba" in block:
                    h, conv[ssm_at], ssm[ssm_at] = one_mamba(block, h, conv[ssm_at], ssm[ssm_at])
                    ssm_at += 1
                elif "attn" in block:
                    with jax.named_scope("attn"):
                        out, kv[kv_at] = attn(
                            block["attn"], rms_norm(h, block["norm"], cfg.layer_norm_epsilon),
                            kv[kv_at])
                    h, kv_at = h + out, kv_at + 1
                else:
                    h, ids, sizes = one_sparse(block, h)
                    chosen.append(ids)
                    loads.append(sizes)
    cache = {"kv": tuple(kv), "ssm": tuple(ssm), "conv": tuple(conv)}
    return h, cache, jnp.stack(chosen), jnp.stack(loads)


# --- the two programs -----------------------------------------------------


class Prefill(NamedTuple):
    logits: jax.Array   # [vocab_held] float32, at the prompt's last position
    cache: dict         # `state_shapes`: the request's state after the prompt
    loads: jax.Array    # [E blocks, held] pairs on each held expert
    chosen: jax.Array | None  # [E blocks, T, k] experts chosen (the parity check reads it)


class Decode(NamedTuple):
    ids: jax.Array      # [steps]
    loads: jax.Array    # [E blocks, held], summed over the steps
    cache: dict         # the state it was given, after the steps
    logits: jax.Array | None  # [steps, vocab_held] float32, after id i; under `collect`
    chosen: jax.Array | None  # [steps, E blocks, k]; under `collect`


@partial(jax.jit, static_argnames=("cfg", "cache_len", "collect"))
def prefill(cfg: NemotronHConfig, params, ids, *, cache_len: int, collect: bool = False):
    """The whole prompt `ids` [T] at once. Returns the logits at its last
    position, the request's state (allocated here, once: the first T
    positions of every `kv` written, `ssm` and `conv` as the last token
    left them), the pairs that fell on each held expert and the experts
    chosen, whatever `collect`: one program (`deepseek_v2.prefill`)."""
    h = params["embed"][ids]
    cache = zeros(state_shapes(cfg, cache_len, h.dtype))
    h, cache, chosen, loads = walk(cfg, params["blocks"], h, cache, partial(attn_whole, cfg))
    return Prefill(head(cfg, params, h[-1:])[0], cache, loads, chosen)  # whatever `collect`


def decode_step(cfg, params, cache, token, position):
    """One token through every block over the request's state. Returns
    (logits [vocab_held], cache, ids [E blocks, k], pairs per held
    expert [E blocks, held])."""
    h = params["embed"][token][None]
    h, cache, chosen, loads = walk(
        cfg, params["blocks"], h, cache, partial(attn_cached, cfg, position))
    return head(cfg, params, h)[0], cache, chosen[:, 0], loads


@partial(jax.jit, static_argnames=("cfg", "steps", "collect"), donate_argnames=("cache",))
def decode(cfg: NemotronHConfig, params, cache, logits, start, key, temperature, *,
           steps: int, collect: bool = False):
    """`steps` dependent decode steps in one program, from the prefill's
    `logits` at position `start - 1`: draw id i from the logits, run it
    through the model at position `start + i`. Always `steps` ids, no
    early stop. The state tree is donated, carried through the loop and
    handed back as `cache`. Returns the ids, the pairs on each held
    expert summed over the steps and, under `collect`, every step's
    logits (the logits after id i) and the experts chosen."""

    def step(cache, token, position):
        logits, cache, chosen, loads = decode_step(cfg, params, cache, token, position)
        return logits, cache, loads, (logits, chosen) if collect else None

    cache, ids, loads, kept = decode_loop(step, cache, logits, start, key, temperature, steps)
    return Decode(ids, loads, cache, *(kept or (None, None)))


class NemotronH(LanguageModel):
    """What a bundle's `lm` part is (the contract is in `lm_common`)."""

    _init = staticmethod(init_params)
    _prefill = staticmethod(prefill)
    _decode = staticmethod(decode)

    @property
    def layer_passes(self) -> int:
        return self.cfg.num_hidden_layers

    def read_back(self, prefill: Prefill, decode: Decode) -> tuple:
        """The pairs on each held expert, of either program."""
        return prefill.loads, decode.loads

    def describe(self, cache_len: int) -> dict[str, int]:
        cfg, shapes = self.cfg, state_shapes(self.cfg, cache_len, self.dtype)
        return {
            "layers": cfg.num_hidden_layers,
            "mamba_layers": len(cfg.blocks_of("M")),
            "attention_layers": len(cfg.blocks_of("*")),
            "sparse_layers": len(cfg.blocks_of("E")),
            "experts_held": len(cfg.held_experts),
            "experts_total": cfg.n_routed_experts,
            "cache_bytes": sum(map(nbytes, shapes["kv"])),
            "state_bytes": sum(map(nbytes, shapes["ssm"] + shapes["conv"])),
        }

    def report(self, prompt_tokens: int, new_tokens: int, cache_len: int,
               prefill_loads, decode_loads) -> dict:
        """`describe`, the chunks a Mamba block's prefill walked and, per
        phase, the routing as `moe.report_loads` has it. A step runs one
        position, whose `num_experts_per_tok` experts are distinct, so
        the held experts its kernel calls read are its held pairs."""
        cfg = self.cfg
        attrs = {
            **self.describe(cache_len),
            "prefill_chunks": -(-prompt_tokens // cfg.chunk_size),
            **report_loads(
                cfg.num_experts_per_tok, cfg.n_routed_experts,
                prompt_tokens, new_tokens, prefill_loads, decode_loads,
                decode_route(
                    cfg.num_experts_per_tok, cfg.hidden_size, cfg.moe_intermediate_size,
                    self.dtype, with_gate=False),
                prefill_expert_route=prefill_route(
                    prompt_tokens, cfg.num_experts_per_tok, len(cfg.held_experts),
                    cfg.n_routed_experts, cfg.hidden_size, cfg.moe_intermediate_size, self.dtype,
                    with_gate=False)),
        }
        return {**attrs, "decode_experts_read": attrs["decode_routed_pairs_held"]}
