"""Adapter plane suite (comfyui_distributed_tpu/adapters/): request
parsing + content-hash identity, rank bucketing, merged-vs-segmented
math parity, cross-job executor slot isolation (bit-exact, jitted +
eager), one-program-per-rank-bucket compile guard, operand LRU cache +
admission cost, and the store/usage threading seams."""

import asyncio
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from comfyui_distributed_tpu.adapters import (
    AdapterError,
    AdapterSpec,
    adapter_plan_key,
    adapter_signature,
    bundle_target_map,
    get_adapter_catalog,
    parse_adapter_specs,
    specs_from_wire,
    specs_to_wire,
)
from comfyui_distributed_tpu.adapters.cache import (
    AdapterOperandCache,
    adapter_admission_cost,
    operands_for_plan,
)
from comfyui_distributed_tpu.adapters.registry import AdapterCatalog
from comfyui_distributed_tpu.adapters.segmented import (
    SegmentOperands,
    build_operands,
    compose_operands,
    make_adapter_step,
    patch_params,
    rank_bucket_for,
    rank_buckets,
)
from comfyui_distributed_tpu.graph.batch_executor import (
    CrossJobExecutor,
    XJobHandle,
)
from comfyui_distributed_tpu.parallel.seeds import fold_job_key


def run(coro):
    return asyncio.run(coro)


# --------------------------------------------------------------------------
# request parsing + plan identity
# --------------------------------------------------------------------------


class TestParse:
    def test_none_and_empty_are_no_plan(self):
        assert parse_adapter_specs(None) == []
        assert parse_adapter_specs([]) == []

    def test_bare_string_defaults_strength(self):
        specs = parse_adapter_specs(["style"])
        assert specs == [AdapterSpec("style", 1.0, "")]

    def test_dict_entries(self):
        specs = parse_adapter_specs(
            [{"name": "a", "strength": 0.5}, {"name": "b"}]
        )
        assert [s.name for s in specs] == ["a", "b"]
        assert specs[0].strength == 0.5
        assert specs[1].strength == 1.0

    @pytest.mark.parametrize(
        "raw,fragment",
        [
            ("not-a-list", "must be a list"),
            ([{"strength": 1.0}], "name"),
            ([{"name": ""}], "name"),
            ([{"name": "a"}, {"name": "a"}], "repeats"),
            ([{"name": "a", "strength": "x"}], "number"),
            ([{"name": "a", "strength": float("nan")}], "finite"),
            ([{"name": "a", "strength": True}], "number"),
            ([42], "object or string"),
        ],
    )
    def test_rejections(self, raw, fragment):
        with pytest.raises(AdapterError, match=fragment):
            parse_adapter_specs(raw)

    def test_cap_at_max_adapters(self):
        raw = [{"name": f"a{i}"} for i in range(5)]
        with pytest.raises(AdapterError, match="at most 4"):
            parse_adapter_specs(raw)

    def test_wire_round_trip(self):
        specs = [
            AdapterSpec("a", 0.5, "ff" * 16),
            AdapterSpec("b", 1.5, "ee" * 16),
        ]
        assert specs_from_wire(specs_to_wire(specs)) == specs


class TestPlanKey:
    def test_unresolved_spec_raises(self):
        with pytest.raises(AdapterError, match="no content hash"):
            adapter_plan_key([AdapterSpec("a", 1.0, "")])

    def test_key_is_hash_strength_pairs_in_order(self):
        specs = [AdapterSpec("a", 0.5, "h1"), AdapterSpec("b", 1.0, "h2")]
        assert adapter_plan_key(specs) == (("h1", 0.5), ("h2", 1.0))

    def test_order_is_significant(self):
        a = [AdapterSpec("a", 1.0, "h1"), AdapterSpec("b", 1.0, "h2")]
        b = [AdapterSpec("b", 1.0, "h2"), AdapterSpec("a", 1.0, "h1")]
        assert adapter_plan_key(a) != adapter_plan_key(b)


# --------------------------------------------------------------------------
# rank buckets
# --------------------------------------------------------------------------


class TestRankBuckets:
    def test_defaults(self):
        assert rank_buckets() == (4, 8, 16, 32, 64)

    def test_bucket_for_rounds_up(self):
        assert rank_bucket_for(1) == 4
        assert rank_bucket_for(4) == 4
        assert rank_bucket_for(5) == 8
        assert rank_bucket_for(64) == 64

    def test_over_max_raises(self):
        with pytest.raises(AdapterError, match="exceeds the largest"):
            rank_bucket_for(65)

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("CDT_ADAPTER_RANK_BUCKETS", "2,16")
        assert rank_buckets() == (2, 16)
        assert rank_bucket_for(3) == 16

    @pytest.mark.parametrize("raw", ["abc", "0,4", "-4,8", ""])
    def test_bad_env_raises(self, monkeypatch, raw):
        monkeypatch.setenv("CDT_ADAPTER_RANK_BUCKETS", raw)
        with pytest.raises(AdapterError):
            rank_buckets()


# --------------------------------------------------------------------------
# catalog: content-hash identity + hash verification
# --------------------------------------------------------------------------


def _tiny_sd(seed=0, rank=2, dim=4, name="lora_unet_foo"):
    rng = np.random.default_rng(seed)
    return {
        f"{name}.lora_down.weight": rng.normal(size=(rank, dim)).astype(
            np.float32
        ),
        f"{name}.lora_up.weight": rng.normal(size=(dim, rank)).astype(
            np.float32
        ),
        # a 0-d ndarray: safetensors' save_file takes arrays, not numpy scalars
        f"{name}.alpha": np.asarray(rank, dtype=np.float32),
    }


class TestCatalog:
    def test_content_hash_is_content_not_name(self):
        cat = AdapterCatalog()
        cat.register_memory("a", _tiny_sd(seed=1))
        cat.register_memory("same-bytes", _tiny_sd(seed=1))
        cat.register_memory("b", _tiny_sd(seed=2))
        assert cat.content_hash("a") == cat.content_hash("same-bytes")
        assert cat.content_hash("a") != cat.content_hash("b")

    def test_resolve_stamps_hashes(self):
        cat = AdapterCatalog()
        cat.register_memory("a", _tiny_sd())
        (resolved,) = cat.resolve([AdapterSpec("a", 0.7)])
        assert resolved.content_hash == cat.content_hash("a")
        assert resolved.strength == 0.7

    def test_resolve_verifies_master_stamp(self):
        cat = AdapterCatalog()
        cat.register_memory("a", _tiny_sd(seed=1))
        good = cat.content_hash("a")
        # same hash passes
        cat.resolve([AdapterSpec("a", 1.0, good)])
        # divergent local bytes (same name) must fail loudly
        cat.register_memory("a", _tiny_sd(seed=2))
        with pytest.raises(AdapterError, match="content mismatch"):
            cat.resolve([AdapterSpec("a", 1.0, good)])

    def test_unknown_name_raises(self):
        with pytest.raises(AdapterError, match="unknown adapter"):
            AdapterCatalog().resolve([AdapterSpec("missing", 1.0)])

    def test_file_resolution_via_lora_dir(self, tmp_path, monkeypatch):
        from safetensors.numpy import save_file

        sd = _tiny_sd(seed=3)
        save_file(sd, str(tmp_path / "style.safetensors"))
        monkeypatch.setenv("CDT_LORA_DIR", str(tmp_path))
        cat = AdapterCatalog()
        assert "style" in cat.names()
        (resolved,) = cat.resolve([AdapterSpec("style", 1.0)])
        assert resolved.content_hash
        loaded = cat.load_state_dict("style")
        np.testing.assert_array_equal(
            loaded["lora_unet_foo.lora_down.weight"],
            sd["lora_unet_foo.lora_down.weight"],
        )

    def test_global_catalog_singleton(self):
        assert get_adapter_catalog() is get_adapter_catalog()


# --------------------------------------------------------------------------
# merged-vs-segmented parity (the numerics contract)
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_bundle():
    from comfyui_distributed_tpu.models import pipeline as pl

    return pl.load_pipeline("tiny-unet", seed=0)


def _flat_unet(tree):
    from comfyui_distributed_tpu.models.io import flatten_params

    return flatten_params(jax.device_get(tree["unet"]))


DENSE_NAME = "lora_unet_input_blocks_1_1_transformer_blocks_0_attn1_to_q"
PROJ_NAME = "lora_unet_input_blocks_1_1_proj_in"


def _lora_for(target_map, name, seed=0, rank=4, alpha=2.0, conv=False):
    rng = np.random.default_rng(seed)
    _, (dim_in, dim_out) = target_map[name]
    down = rng.normal(size=(rank, dim_in)).astype(np.float32)
    up = rng.normal(size=(dim_out, rank)).astype(np.float32)
    if conv:  # conv1x1-style layout some trainers emit for proj layers
        down = down[:, :, None, None]
        up = up[:, :, None, None]
    return {
        f"{name}.lora_down.weight": down,
        f"{name}.lora_up.weight": up,
        f"{name}.alpha": np.float32(alpha),
    }


class TestSegmentedParity:
    @pytest.mark.parametrize(
        "name,conv",
        [(DENSE_NAME, False), (PROJ_NAME, True)],
        ids=["dense", "proj-conv1x1"],
    )
    def test_patch_params_matches_merged_loader(
        self, tiny_bundle, name, conv
    ):
        """patch_params (the elastic whole-grant variant) lands on the
        same kernels as models/lora.apply_lora for both target
        families, including the strength scale."""
        from comfyui_distributed_tpu.models import get_config
        from comfyui_distributed_tpu.models.lora import apply_lora

        target_map = bundle_target_map(tiny_bundle)
        sd = _lora_for(target_map, name, seed=5, conv=conv)
        merged, unmatched = apply_lora(
            {"unet": tiny_bundle.params["unet"]},
            sd,
            get_config("tiny-unet"),
            strength=0.7,
        )
        assert unmatched == []
        ops = build_operands(sd, target_map)
        patched = patch_params(tiny_bundle.params, ops, scale=0.7)
        path = target_map[name][0][len("unet/"):]
        np.testing.assert_allclose(
            _flat_unet(patched)[path], _flat_unet(merged)[path], rtol=1e-5
        )
        # a leaf the adapter does not touch is BIT-identical: the zero
        # operand rows contribute exactly 0.0
        other = next(
            p[len("unet/"):]
            for n, (p, _) in sorted(target_map.items())
            if n != name
        )
        np.testing.assert_array_equal(
            _flat_unet(patched)[other],
            _flat_unet({"unet": tiny_bundle.params["unet"]})[other],
        )

    def test_rank_padding_is_exact(self, tiny_bundle):
        """The same adapter padded to a LARGER rank bucket produces
        bit-identical patched kernels — zero rows are exact."""
        target_map = bundle_target_map(tiny_bundle)
        sd = _lora_for(target_map, DENSE_NAME, seed=6, rank=3)
        small = build_operands(sd, target_map, bucket=4)
        large = build_operands(sd, target_map, bucket=8)
        assert small.rank_bucket == 4 and large.rank_bucket == 8
        path = target_map[DENSE_NAME][0][len("unet/"):]
        a = _flat_unet(patch_params(tiny_bundle.params, small, scale=1.3))
        b = _flat_unet(patch_params(tiny_bundle.params, large, scale=1.3))
        np.testing.assert_array_equal(a[path], b[path])

    def test_bucket_smaller_than_rank_raises(self, tiny_bundle):
        target_map = bundle_target_map(tiny_bundle)
        sd = _lora_for(target_map, DENSE_NAME, rank=6)
        with pytest.raises(AdapterError, match="exceeds requested bucket"):
            build_operands(sd, target_map, bucket=4)

    def test_te_modules_are_skipped_not_fatal(self, tiny_bundle):
        """lora_te* rides only the merged loader; the backbone-only
        segmented tier skips it and still builds unet operands."""
        target_map = bundle_target_map(tiny_bundle)
        sd = _lora_for(target_map, DENSE_NAME, seed=7)
        sd.update(_tiny_sd(name="lora_te_text_model_encoder_layers_0_mlp_fc1"))
        ops = build_operands(sd, target_map)
        assert any(np.abs(d).sum() > 0 for d in ops.downs)

    def test_compose_matches_sequential_merge(self, tiny_bundle):
        """Two stacked adapters (rank concat, strengths folded) land on
        the same kernels as merging them one after the other."""
        from comfyui_distributed_tpu.models import get_config
        from comfyui_distributed_tpu.models.lora import apply_lora

        cfg = get_config("tiny-unet")
        target_map = bundle_target_map(tiny_bundle)
        sd_a = _lora_for(target_map, DENSE_NAME, seed=8, rank=2)
        sd_b = _lora_for(target_map, PROJ_NAME, seed=9, rank=3)
        merged, _ = apply_lora(
            {"unet": tiny_bundle.params["unet"]}, sd_a, cfg, strength=0.5
        )
        merged, _ = apply_lora(merged, sd_b, cfg, strength=1.5)
        ops_a = build_operands(sd_a, target_map)
        ops_b = build_operands(sd_b, target_map)
        composed = compose_operands([ops_a, ops_b], [0.5, 1.5])
        assert composed.rank_bucket >= ops_a.rank_bucket + ops_b.rank_bucket
        assert composed.scale == 1.0
        patched = patch_params(tiny_bundle.params, composed)
        for name in (DENSE_NAME, PROJ_NAME):
            path = target_map[name][0][len("unet/"):]
            np.testing.assert_allclose(
                _flat_unet(patched)[path],
                _flat_unet(merged)[path],
                rtol=1e-5,
                atol=1e-6,
            )

    def test_compose_rejects_mismatched_target_maps(self):
        a = SegmentOperands(("p1",), (np.zeros((4, 2), np.float32),),
                            (np.zeros((2, 4), np.float32),), 1.0, 4, 0, "a")
        b = SegmentOperands(("p2",), (np.zeros((4, 2), np.float32),),
                            (np.zeros((2, 4), np.float32),), 1.0, 4, 0, "b")
        with pytest.raises(AdapterError, match="different target maps"):
            compose_operands([a, b], [1.0, 1.0])


# --------------------------------------------------------------------------
# signature extension
# --------------------------------------------------------------------------


def _stub_ops(seed=0, rank=2, bucket=4, dim=3, scale=1.0,
              paths=("unet/dense/kernel",)):
    """Hand-built operands against a toy params tree (the executor
    tests' target map: one 3x3 kernel)."""
    rng = np.random.default_rng(seed)
    downs, ups = [], []
    for _ in paths:
        down = np.zeros((bucket, dim), np.float32)
        up = np.zeros((dim, bucket), np.float32)
        down[:rank] = 0.1 * rng.normal(size=(rank, dim))
        up[:, :rank] = 0.1 * rng.normal(size=(dim, rank))
        downs.append(down)
        ups.append(up)
    nbytes = sum(a.nbytes for a in downs) + sum(a.nbytes for a in ups)
    return SegmentOperands(
        paths=tuple(paths), downs=tuple(downs), ups=tuple(ups),
        scale=float(scale), rank_bucket=bucket, nbytes=nbytes,
        fingerprint=f"stub-{seed}",
    )


class TestSignature:
    def test_extends_base_signature(self):
        sig = adapter_signature(("stub", 1), _stub_ops())
        assert sig[:2] == ("stub", 1)
        kind, bucket, digest = sig[-1]
        assert kind == "adapter" and bucket == 4 and digest

    def test_same_bucket_different_content_shares_signature(self):
        # content and strength are traced operands — NOT signature
        base = ("stub",)
        a = adapter_signature(base, _stub_ops(seed=1, scale=0.5))
        b = adapter_signature(base, _stub_ops(seed=2, scale=2.0))
        assert a == b

    def test_bucket_changes_signature(self):
        base = ("stub",)
        assert adapter_signature(base, _stub_ops(bucket=4)) != (
            adapter_signature(base, _stub_ops(bucket=8))
        )

    def test_target_paths_change_signature(self):
        base = ("stub",)
        assert adapter_signature(base, _stub_ops()) != adapter_signature(
            base, _stub_ops(paths=("unet/other/kernel",))
        )


# --------------------------------------------------------------------------
# executor: slot isolation + compile-count guard
# --------------------------------------------------------------------------

N_STEPS = 3


def _params(dim=3):
    # identity-ish kernel so the matmul path stays well-conditioned
    return {
        "unet": {
            "dense": {"kernel": jnp.eye(dim, dtype=jnp.float32) * 0.9}
        }
    }


def _weight_proc(n_steps=N_STEPS, signature=("wstub",), jit=False,
                 trace_log=None):
    """A stepwise stub whose step actually CONSUMES the params kernel,
    so a per-slot weight patch is visible in the output."""

    def init(params, tile, key):
        return tile + 0.0

    def step(params, x, key, pos, neg, yx, i):
        if trace_log is not None:
            trace_log.append(1)
        w = params["unet"]["dense"]["kernel"]
        ki = jax.random.fold_in(key, i)
        return (
            jnp.einsum("hwc,cd->hwd", x, w)
            + 0.01 * jax.random.normal(ki, x.shape)
            + 0.001 * pos
        )

    def finish(params, x):
        return jnp.clip(x, -10.0, 10.0)

    return types.SimpleNamespace(
        init=init,
        step=jax.jit(step) if jit else step,
        finish=finish,
        n_steps=n_steps,
        signature=tuple(signature),
    )


class _FakeMaster:
    def __init__(self, n_tiles):
        self.pending = list(range(n_tiles))

    def pull(self):
        if not self.pending:
            return None
        grant, self.pending = self.pending, []
        return {"tile_idxs": grant, "checkpoints": {}}

    def release(self, idxs, cks):
        self.pending = sorted(set(self.pending) | set(idxs))


def _make_job(job_id, n_tiles, seed, *, proc, params, adapter=None):
    master = _FakeMaster(n_tiles)
    rng = np.random.default_rng(seed)
    extracted = jnp.asarray(rng.random((n_tiles, 4, 4, 3)), jnp.float32)
    outs = {}
    handle = XJobHandle(
        job_id=job_id,
        proc=proc,
        params=params,
        extracted=extracted,
        positions=jnp.zeros((n_tiles, 2), jnp.int32),
        pos=jnp.float32(seed),
        neg=jnp.float32(0),
        base_key=fold_job_key(jax.random.key(seed), job_id),
        pull=master.pull,
        emit=lambda idx, arr: outs.__setitem__(int(idx), np.asarray(arr)),
        flush=lambda final: None,
        release=master.release,
        adapter=adapter,
    )
    return handle, outs


def _solo(job_id, n_tiles, seed, *, proc, params, adapter=None, k_max=8):
    ex = CrossJobExecutor(k_max=k_max)
    handle, outs = _make_job(
        job_id, n_tiles, seed, proc=proc, params=params, adapter=adapter
    )
    ex.register(handle)
    ex.run()
    return outs


class TestExecutorSlotIsolation:
    @pytest.mark.parametrize("jit", [False, True], ids=["eager", "jitted"])
    def test_different_adapters_batched_bit_identical_to_solo(self, jit):
        """Two jobs wearing DIFFERENT adapters share one batch (same
        rank bucket → same extended signature) and each tile's output
        is bit-identical to sampling that job alone."""
        proc = _weight_proc(jit=jit)
        params = _params()
        ops_a = _stub_ops(seed=1, scale=0.8)
        ops_b = _stub_ops(seed=2, scale=1.2)
        ex = CrossJobExecutor(k_max=4)
        h1, o1 = _make_job("job-a", 2, 1, proc=proc, params=params,
                           adapter=ops_a)
        h2, o2 = _make_job("job-b", 2, 2, proc=proc, params=params,
                           adapter=ops_b)
        assert h1.sig == h2.sig  # they CAN batch together
        ex.register(h1)
        ex.register(h2)
        ex.run()
        solo_a = _solo("job-a", 2, 1, proc=proc, params=params,
                       adapter=ops_a)
        solo_b = _solo("job-b", 2, 2, proc=proc, params=params,
                       adapter=ops_b)
        for i in range(2):
            np.testing.assert_array_equal(o1[i], solo_a[i])
            np.testing.assert_array_equal(o2[i], solo_b[i])

    def test_adapter_actually_changes_output(self):
        proc = _weight_proc()
        params = _params()
        base = _solo("job-a", 1, 1, proc=proc, params=params)
        worn = _solo("job-a", 1, 1, proc=proc, params=params,
                     adapter=_stub_ops(seed=3))
        assert not np.array_equal(base[0], worn[0])

    def test_adapterless_keeps_original_signature_and_output(self):
        """An adapter-less job never shares a signature group with
        adapter jobs, and its output is bit-identical to a run where
        the adapter plane does not exist at all."""
        proc = _weight_proc()
        params = _params()
        h_plain, _ = _make_job("plain", 1, 5, proc=proc, params=params)
        h_worn, _ = _make_job("worn", 1, 6, proc=proc, params=params,
                              adapter=_stub_ops(seed=4))
        assert h_plain.sig == proc.signature
        assert h_worn.sig != proc.signature

        ex = CrossJobExecutor(k_max=4)
        hp, op_ = _make_job("plain", 1, 5, proc=proc, params=params)
        hw, _ = _make_job("worn", 1, 6, proc=proc, params=params,
                          adapter=_stub_ops(seed=4))
        ex.register(hp)
        ex.register(hw)
        ex.run()
        baseline = _solo("plain", 1, 5, proc=proc, params=params)
        np.testing.assert_array_equal(op_[0], baseline[0])

    def test_mixed_strengths_ride_as_traced_scale(self):
        """Same adapter content at different strengths batches under
        one signature and stays bit-identical to solo."""
        proc = _weight_proc(jit=True)
        params = _params()
        weak = _stub_ops(seed=7, scale=0.25)
        strong = _stub_ops(seed=7, scale=4.0)
        ex = CrossJobExecutor(k_max=4)
        h1, o1 = _make_job("weak", 1, 1, proc=proc, params=params,
                           adapter=weak)
        h2, o2 = _make_job("strong", 1, 1, proc=proc, params=params,
                           adapter=strong)
        ex.register(h1)
        ex.register(h2)
        ex.run()
        np.testing.assert_array_equal(
            o1[0], _solo("weak", 1, 1, proc=proc, params=params,
                         adapter=weak)[0]
        )
        np.testing.assert_array_equal(
            o2[0], _solo("strong", 1, 1, proc=proc, params=params,
                         adapter=strong)[0]
        )
        assert not np.array_equal(o1[0], o2[0])


class TestCompileGuard:
    def test_n_distinct_adapters_one_trace(self):
        """N jobs wearing N DIFFERENT same-rank adapters run under ONE
        traced program: adapter content is an operand, not a signature.
        Trace count == compile count for a jitted step."""
        trace_log = []
        proc = _weight_proc(jit=True, trace_log=trace_log)
        params = _params()
        ex = CrossJobExecutor(k_max=4)
        handles = []
        for i in range(3):
            h, _ = _make_job(f"job-{i}", 1, i + 1, proc=proc, params=params,
                             adapter=_stub_ops(seed=10 + i))
            handles.append(h)
            ex.register(h)
        ex.run()
        assert all(h.done and h.error is None for h in handles)
        # every dispatch is the same (signature, bucket): one trace
        assert len(trace_log) == 1


# --------------------------------------------------------------------------
# operand cache + admission cost
# --------------------------------------------------------------------------


class TestOperandCache:
    def test_hit_miss_accounting(self):
        cache = AdapterOperandCache(budget_bytes=1 << 20)
        ops = _stub_ops(seed=1)
        built = []

        def build():
            built.append(1)
            return ops

        got, hit = cache.get_or_build(("k1",), ("h1",), build)
        assert got is ops and not hit
        got, hit = cache.get_or_build(("k1",), ("h1",), build)
        assert got is ops and hit
        assert len(built) == 1
        assert cache.stats()["hits"] == 1
        assert cache.stats()["misses"] == 1

    def test_lru_eviction_under_budget(self):
        ops = _stub_ops(seed=1)
        cache = AdapterOperandCache(budget_bytes=int(ops.nbytes * 2.5))
        for i in range(3):
            cache.get_or_build((f"k{i}",), (f"h{i}",), lambda: ops)
        stats = cache.stats()
        assert stats["evictions"] >= 1
        assert stats["bytes"] <= cache.budget_bytes
        # oldest entry (and its hash ref) evicted first
        assert not cache.contains_hash("h0")
        assert cache.contains_hash("h2")

    def test_oversized_entry_not_cached(self):
        ops = _stub_ops(seed=1)
        cache = AdapterOperandCache(budget_bytes=ops.nbytes - 1)
        got, hit = cache.get_or_build(("k",), ("h",), lambda: ops)
        assert got is ops and not hit
        assert cache.stats()["entries"] == 0
        assert not cache.contains_hash("h")

    def test_operands_for_plan_strength_independent_caching(self):
        cat = AdapterCatalog()
        cat.register_memory("a", _tiny_sd(seed=1, dim=3, name="lora_unet_x"))
        (spec,) = cat.resolve([AdapterSpec("a", 0.5)])
        target_map = {"lora_unet_x": ("unet/dense/kernel", (3, 3))}
        cache = AdapterOperandCache(budget_bytes=1 << 20)
        ops1 = operands_for_plan([spec], target_map, catalog=cat, cache=cache)
        ops2 = operands_for_plan(
            [AdapterSpec("a", 2.0, spec.content_hash)],
            target_map, catalog=cat, cache=cache,
        )
        assert cache.stats()["misses"] == 1
        assert cache.stats()["hits"] == 1  # strength sweep reuses entry
        assert ops1.scale == 0.5 and ops2.scale == 2.0
        np.testing.assert_array_equal(ops1.downs[0], ops2.downs[0])

    def test_operands_for_plan_empty_or_unresolved_raises(self):
        with pytest.raises(AdapterError, match="empty plan"):
            operands_for_plan([], {})
        with pytest.raises(AdapterError, match="no content hash"):
            operands_for_plan(
                [AdapterSpec("a", 1.0)],
                {"lora_unet_x": ("unet/dense/kernel", (3, 3))},
            )

    def test_admission_cost_knob(self, monkeypatch):
        # default (1.0) = seam off, even for unknown hashes
        assert adapter_admission_cost(("deadbeef",)) == 1.0
        monkeypatch.setenv("CDT_ADAPTER_COLD_COST", "2.5")
        assert adapter_admission_cost(()) == 1.0
        assert adapter_admission_cost(("not-resident",)) == 2.5

    def test_admission_cost_warm_plan_is_free(self, monkeypatch):
        from comfyui_distributed_tpu.adapters.cache import (
            _reset_adapter_cache_for_tests,
            get_adapter_cache,
        )

        monkeypatch.setenv("CDT_ADAPTER_COLD_COST", "3.0")
        _reset_adapter_cache_for_tests()
        try:
            cache = get_adapter_cache()
            cache.get_or_build(("k",), ("warmhash",), lambda: _stub_ops())
            assert adapter_admission_cost(("warmhash",)) == 1.0
            assert adapter_admission_cost(("warmhash", "coldhash")) == 3.0
        finally:
            _reset_adapter_cache_for_tests()


# --------------------------------------------------------------------------
# store threading + usage attribution
# --------------------------------------------------------------------------

WIRE = [{"name": "style", "strength": 0.5, "content_hash": "ab" * 16}]


class TestStoreThreading:
    def test_note_then_init_stamps_plan(self):
        from comfyui_distributed_tpu.jobs import JobStore

        store = JobStore()

        async def scenario():
            store.note_job_adapters("t", WIRE)
            assert await store.peek_job_adapters("t") == WIRE
            job = await store.init_tile_job("t", [0, 1])
            assert job.adapters == WIRE
            # stamped record now answers the peek (non-destructive)
            assert await store.peek_job_adapters("t") == WIRE

        run(scenario())

    def test_malformed_note_is_dropped(self):
        from comfyui_distributed_tpu.jobs import JobStore

        store = JobStore()
        store.note_job_adapters("t", [{"strength": 2.0}])  # no name

        async def scenario():
            assert await store.peek_job_adapters("t") == []
            job = await store.init_tile_job("t", [0])
            assert job.adapters == []

        run(scenario())

    def test_journal_replay_restores_plan(self, tmp_path):
        """job_init journals the wire plan; recovery re-serves it so a
        restarted master's job_status still carries the adapters."""
        from comfyui_distributed_tpu.durability.journal import Journal
        from comfyui_distributed_tpu.durability.recovery import recover_state

        journal = Journal(str(tmp_path), fsync_every=1)
        journal.append(
            {"type": "job_init", "job": "j", "kind": "tile",
             "batched": True, "tasks": [0, 1], "adapters": WIRE}
        )
        journal.close()
        state, _ = recover_state(str(tmp_path))
        assert state["jobs"]["j"]["adapters"] == WIRE

    def test_recovered_store_serves_plan(self, tmp_path):
        from comfyui_distributed_tpu.durability.journal import Journal
        from comfyui_distributed_tpu.durability.recovery import recover
        from comfyui_distributed_tpu.jobs import JobStore

        journal = Journal(str(tmp_path), fsync_every=1)
        journal.append(
            {"type": "job_init", "job": "j", "kind": "tile",
             "batched": True, "tasks": [0], "adapters": WIRE}
        )
        journal.close()
        store = JobStore()
        recover(str(tmp_path), store)

        async def scenario():
            assert await store.peek_job_adapters("j") == WIRE

        run(scenario())

    def test_legacy_record_without_adapters_restores_empty(self, tmp_path):
        from comfyui_distributed_tpu.durability.journal import Journal
        from comfyui_distributed_tpu.durability.recovery import recover_state

        journal = Journal(str(tmp_path), fsync_every=1)
        journal.append(
            {"type": "job_init", "job": "j", "kind": "tile",
             "batched": True, "tasks": [0]}
        )
        journal.close()
        state, _ = recover_state(str(tmp_path))
        assert state["jobs"]["j"]["adapters"] == []


class TestUsageAttribution:
    def test_rollup_gains_adapter_section(self):
        from comfyui_distributed_tpu.telemetry.usage import UsageMeter

        meter = UsageMeter(clock=lambda: 0.0)
        meter.note_job_attrs("j1", "tenant-a", "")
        meter.note_job_adapter("j1", "hash1:0.5")
        meter.note_dispatch(
            tier="xjob", role="worker", elapsed_s=1.0, chips=1,
            slots=[{"job_id": "j1", "kind": "real"}],
        )
        meter.note_tiles("worker", "j1", 2)
        roll = meter.rollup()
        assert "hash1:0.5" in roll["adapters"]
        assert roll["adapters"]["hash1:0.5"]["tiles"] == 2
        assert roll["jobs"]["j1"]["adapter"] == "hash1:0.5"

    def test_adapterless_job_absent_from_adapter_rollup(self):
        from comfyui_distributed_tpu.telemetry.usage import UsageMeter

        meter = UsageMeter(clock=lambda: 0.0)
        meter.note_dispatch(
            tier="xjob", role="worker", elapsed_s=1.0, chips=1,
            slots=[{"job_id": "j1", "kind": "real"}],
        )
        roll = meter.rollup()
        assert roll["adapters"] == {}
        assert roll["jobs"]["j1"]["adapter"] == ""


# --------------------------------------------------------------------------
# adapter step wrapper (unit)
# --------------------------------------------------------------------------


class TestAdapterStep:
    def test_wrapper_patches_then_delegates(self):
        seen = {}

        def base_step(params, x, key, pos, neg, yx, i):
            seen["kernel"] = params["unet"]["dense"]["kernel"]
            return x

        ops = _stub_ops(seed=1)
        step = make_adapter_step(base_step, ops.paths)
        params = _params()
        x = jnp.zeros((4, 4, 3), jnp.float32)
        step(params, x, jax.random.key(0), 0.0, 0.0,
             jnp.zeros(2, jnp.int32), 0,
             tuple(jnp.asarray(d) for d in ops.downs),
             tuple(jnp.asarray(u) for u in ops.ups),
             jnp.float32(ops.scale))
        expect = np.asarray(
            params["unet"]["dense"]["kernel"], np.float32
        ) + ops.scale * (ops.downs[0].T @ ops.ups[0].T)
        np.testing.assert_allclose(
            np.asarray(seen["kernel"]), expect, rtol=1e-5
        )
        # the original tree is untouched (copy-on-write)
        np.testing.assert_array_equal(
            np.asarray(params["unet"]["dense"]["kernel"]),
            np.asarray(_params()["unet"]["dense"]["kernel"]),
        )
