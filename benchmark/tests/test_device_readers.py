"""The readers of what the device did, program by program. Eleven read the
device's own "XLA Modules" line of the trace a run keeps (here a small
.xplane.pb written by hand, found where run.py's `--out` says), one the
program's `device.run` spans; each leaves its metric out where it finds
nothing; the shares of a peak are held against `deepseek_counts` /
`ouro_counts` / `flux_counts` by hand; and the by-hand timeline's gap
table and lateness.

    python -m pytest benchmark/tests -q
"""

import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import deepseek_counts  # noqa: E402
import device_modules  # noqa: E402
import device_spans  # noqa: E402
import device_timeline  # noqa: E402
import flux_counts  # noqa: E402
import ouro_counts  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
    PER_LAYER = {m["name"]: m for m in json.load(fh)["per_layer"]}
NEW = ["sampler_device_ms.txt2img", "vae_device_ms.txt2img", "tile_device_ms.usdu",
       "prefill_device_ms.lm", "decode_device_ms_per_token.lm", "decode_hbm_roofline_pct.lm",
       "prefill_mxu_peak_pct.lm", "mfu_pct.flux", "device_idle_in_pct.txt2img",
       "device_idle_in_pct.usdu", "between_jobs_ms.txt2img", "between_jobs_ms.usdu"]
FROM_SPANS = "tile_device_ms.usdu"
DEEPSEEK_NODE = dict(prompt_tokens=2048, new_tokens=256, layers=5, experts_held=40,
                     experts_total=160, prefill_routed_pairs=2048 * 24,
                     prefill_routed_pairs_held=12000, decode_routed_pairs=256 * 24,
                     decode_routed_pairs_held=1536)
OURO_NODE = dict(prompt_tokens=2048, new_tokens=64, ut_steps=4, layers=48, cache_slots=192)
MS = 1_000_000  # ns


def device_reader(name: str):
    """The metric's read(), loaded as run.py loads it."""
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("layer_metric", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# --- a trace written by hand -----------------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n >> 7 else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def xspace(planes: dict) -> bytes:
    """An XSpace of {plane: {line: [(event name, start_ns, end_ns)]}}."""
    out = b""
    for plane_id, (plane_name, lines) in enumerate(planes.items(), 1):
        ids = {n: i for i, n in enumerate(sorted({e[0] for es in lines.values() for e in es}), 1)}
        plane = _field(1, plane_id) + _field(2, plane_name)
        for line_id, (line_name, events) in enumerate(lines.items(), 1):
            line = _field(1, line_id) + _field(2, line_name) + _field(3, 0)
            for name, start, end in events:
                line += _field(4, _field(1, ids[name]) + _field(2, start * 1000)
                               + _field(3, (end - start) * 1000))
            plane += _field(3, line)
        for name, i in ids.items():
            plane += _field(4, _field(1, i) + _field(2, _field(1, i) + _field(2, name)))
        out += _field(1, plane)
    return out


def tracing(tmp_path, monkeypatch):
    """-> write(modules), which puts them on the first device's "XLA
    Modules" line of a trace under <out>/profile, `out` being where this
    process's `--out` points, as a run.py's would. No fixture: the tier-1
    command adopts this file's test functions alone."""
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", "a.cell", "--seed", "1",
                                      "--out", str(tmp_path)])

    def write(modules, plane="/device:TPU:0"):
        folder = tmp_path / "profile" / "trace-0001-benchmark" / "plugins" / "profile" / "x"
        folder.mkdir(parents=True, exist_ok=True)
        named = [(f"{kind}({7 + i})", start, end) for i, (kind, start, end) in enumerate(modules)]
        (folder / "vm.xplane.pb").write_bytes(xspace({
            "/host:CPU": {"python": [("device.watch", 0, 5 * MS)]},
            plane: {"XLA Ops": [("%fusion.1 = bf16[8]{0} fusion(", MS, 2 * MS)],
                    "XLA Modules": named},
            "/device:TPU:1": {"XLA Modules": [("jit_other(1)", 0, MS)]},
        }))
        device_modules._LOADED.clear()

    return write


def txt2img_modules(jobs, period, sampler=400, vae=20, gap=15, first=()):
    """`jobs` jobs on the device's clock, ms: [first programs,] the key,
    the sampler, the decode, a cast; then `gap` ms with nothing but that."""
    out, at = [], 0
    for _ in range(jobs):
        start = at
        for kind, ms in first:
            out.append((kind, at * MS, (at + ms) * MS))
            at += ms
        out.append(("jit__threefry_seed", at * MS, at * MS + 700))
        out.append(("jit__img2img_jit", at * MS + 1000, (at + sampler) * MS))
        out.append(("jit_vae_apply", (at + sampler) * MS, (at + sampler + vae) * MS))
        out.append(("jit_convert_element_type", (at + sampler + vae + 1) * MS,
                    (at + sampler + vae + 1) * MS + 600))
        at = start + period
    return out


def host_span(name, start, end, span_id=None, parent_id="e", **attrs):
    return {"name": name, "span_id": span_id or name, "parent_id": parent_id, "start": start,
            "end": end, "duration": end - start, "attrs": attrs, "status": "ok"}


def device_run(program, start, begin, end, status="ok"):
    attrs = {"program": program}
    if status == "ok":
        attrs.update(begin=begin, queued_s=begin - start, busy_s=end - begin)
    return {"name": "device.run", "span_id": f"{program}@{start}", "parent_id": "n",
            "start": start, "end": end, "duration": end - start, "attrs": attrs,
            "status": status}


def txt2img_job(at, sampler_s=0.400, vae_s=0.020, evals=40, launches=True):
    """One job on the tracer's clock from `at`: the walk launches the
    sampler 5 ms in and the decode 1 ms later, the device runs one after
    the other, the save's wait ends 6 ms after the device."""
    done = at + 0.005 + sampler_s + vae_s
    spans = [host_span("execute_prompt", at, done + 0.030, parent_id=None, span_id="e"),
             host_span("node.KSampler", at + 0.001, at + 0.006, evals=evals),
             host_span("node.VAEDecode", at + 0.006, at + 0.007),
             host_span("node.SaveImage", at + 0.007, done + 0.008),
             host_span("device.wait", at + 0.007, done + 0.006, parent_id="node.SaveImage",
                       after_ready_s=0.006)]
    if launches:
        spans += [device_run("sampler", at + 0.005, at + 0.005, at + 0.005 + sampler_s),
                  device_run("vae_decode", at + 0.006, at + 0.005 + sampler_s, done)]
    return spans


def lm_job(at, node):
    """A language-model job's node span, which says what it generated."""
    return [host_span("execute_prompt", at, at + 3.0, parent_id=None, span_id="e"),
            host_span("node.TextGenerate", at, at + 2.0, **node)]


def lm_prompt(ckpt_name):
    return {"4": {"class_type": "CheckpointLoaderSimple", "inputs": {"ckpt_name": ckpt_name}},
            "1": {"class_type": "UNETLoader", "inputs": {"unet_name": "sd15"}}}


def window(jobs, prompt=None, trace=True):
    return {"spans": {f"t{i}": job for i, job in enumerate(jobs)}, "records": [],
            "trace": {"busy_s": 1.0, "window_s": 2.0} if trace else None,
            "prompt": prompt or {}}


def test_device_every_new_metric_has_its_reader_and_names_its_cells():
    for name in NEW:
        assert callable(device_reader(name)), name
        assert PER_LAYER[name]["workloads"]
        # a device reading comes from the device's line; the one host reading says so
        assert PER_LAYER[name]["source"] == (
            "program_span" if name == FROM_SPANS else "device_trace"), name
    assert [PER_LAYER[n]["layer"] for n in NEW] == ["sampling programs"] * 8 + ["device"] * 4
    assert list(PER_LAYER)[-len(NEW):] == NEW  # appended, nothing moved


@pytest.mark.parametrize("name", NEW)
def test_device_a_run_with_nothing_to_read_leaves_the_metric_out(name, tmp_path, monkeypatch):
    traced = tracing(tmp_path, monkeypatch)
    jobs = [txt2img_job(0.0, launches=False), txt2img_job(0.5, launches=False)]
    prompt = lm_prompt("deepseek-v2-ep4-5l")
    # no trace on disk, then a trace with no device plane, then an untraced run
    assert device_reader(name)(window(jobs, prompt)) is None
    traced(txt2img_modules(4, 440), plane="/host:other")
    assert device_reader(name)(window(jobs, prompt)) is None
    traced(txt2img_modules(4, 440))
    assert device_reader(name)(window(jobs, prompt, trace=False)) is None
    assert device_reader(name)(window([], trace=False)) is None


def test_device_the_trace_is_found_where_run_py_keeps_it(tmp_path, monkeypatch):
    traced = tracing(tmp_path, monkeypatch)
    assert device_modules.profile_dir() == os.path.join(str(tmp_path), "profile")
    assert device_modules.profile_dir(["--workload", "a.cell", "--seed", "3"]) == os.path.join(
        ROOT, "chiprun_out", "benchmark", "a.cell", "profile")
    assert device_modules.profile_dir(["-q", "tests/"]) is None
    traced([("jit_b", 5 * MS, 9 * MS), ("jit_a", MS, 4 * MS)])
    # the first device plane's programs, by start, named as xplane.kind names them
    assert device_modules.modules(window([])) == [("jit_a", MS, 4 * MS), ("jit_b", 5 * MS, 9 * MS)]


def test_device_seconds_by_program_are_medians_over_the_slices_whole_programs(tmp_path, monkeypatch):
    traced = tracing(tmp_path, monkeypatch)
    # the slice cuts into the first sampler program and out of the last decode
    modules = [("jit__img2img_jit", 0, 130 * MS), ("jit_vae_apply", 130 * MS, 150 * MS)]
    for i, (sampler, vae) in enumerate([(400, 20), (410, 21), (450, 22)]):
        at = 200 + 500 * i
        modules += [("jit__img2img_jit", at * MS, (at + sampler) * MS),
                    ("jit_vae_apply", (at + sampler) * MS, (at + sampler + vae) * MS)]
    modules[-1] = ("jit_vae_apply", modules[-1][1], modules[-1][1] + 3 * MS)
    traced(modules)
    material = window([])
    assert device_reader("sampler_device_ms.txt2img")(material) == pytest.approx(410.0)
    assert device_reader("vae_device_ms.txt2img")(material) == pytest.approx(20.0)  # 20, 20, 21
    assert device_reader("prefill_device_ms.lm")(material) is None


def test_device_the_tile_program_is_read_from_the_programs_own_spans():
    jobs = [txt2img_job(0.0), txt2img_job(0.5)]
    assert device_reader(FROM_SPANS)(window(jobs, trace=False)) is None
    tile = [host_span("node.UltimateSDUpscaleDistributed", 0.0, 11.3),
            device_run("upscale_single", 0.2, 0.2, 11.3)]
    other = [device_run("upscale_single", 12.0, 12.0, 23.3),
             device_run("upscale_single", 0.001, None, 0.002, status="error")]
    assert device_reader(FROM_SPANS)(window([tile, other])) == pytest.approx(11200.0)
    assert device_spans.busy_ms(window([txt2img_job(0.0)]), "sampler") == pytest.approx(400.0)


def test_device_idle_share_and_the_gap_between_jobs_are_read_off_the_devices_line(tmp_path, monkeypatch):
    traced = tracing(tmp_path, monkeypatch)
    # 420 ms of device work every 440 ms; the cast falls into the gap
    traced(txt2img_modules(4, 440))
    material = window([])
    assert device_reader("between_jobs_ms.txt2img")(material) == pytest.approx(20.0, abs=0.01)
    programs_ms = 4 * 420 + 4 * (0.0007 - 0.001 + 0.0006)  # the key fills a microsecond's hole
    assert device_reader("device_idle_in_pct.txt2img")(material) == pytest.approx(
        100.0 * (1 - programs_ms / (3 * 440 + 421.0006)))
    assert device_reader("between_jobs_ms.usdu")(material) is None
    # behind a language model the next job begins with its prefill
    traced(txt2img_modules(3, 1000, first=(("jit_prefill", 100), ("jit_decode", 400))))
    assert device_reader("between_jobs_ms.txt2img")(material) == pytest.approx(80.0, abs=0.01)
    # one tile program a job: the gap is between two of them, whole or cut
    traced([("jit_upscale_single", 0, 3000 * MS), ("jit_convert_element_type", 3001 * MS,
            3001 * MS + 500), ("jit_upscale_single", 3140 * MS, 14200 * MS),
            ("jit_upscale_single", 14350 * MS, 15000 * MS)])
    assert device_reader("between_jobs_ms.usdu")(material) == pytest.approx(145.0)
    assert device_reader("device_idle_in_pct.usdu")(material) == pytest.approx(
        100.0 * (290 - 0.0005) / 15000)
    traced([("jit_upscale_single", 0, 3000 * MS)])
    assert device_reader("between_jobs_ms.usdu")(material) is None
    assert device_reader("device_idle_in_pct.usdu")(material) is None


def lm_modules(jobs, period, prefill_us, decode_us):
    out = []
    for i in range(jobs):
        at = i * period * MS
        out += [("jit_prefill", at, at + prefill_us * 1000),
                ("jit_decode", at + prefill_us * 1000, at + (prefill_us + decode_us) * 1000)]
    return out + [("jit_vae_apply", jobs * period * MS, jobs * period * MS + 3)]


def test_device_decode_step_and_its_roofline_share_against_deepseek_counts_by_hand(tmp_path, monkeypatch):
    """A synthetic 4.20 ms step: the share is what deepseek_counts gives by hand."""
    traced = tracing(tmp_path, monkeypatch)
    traced([("jit__clip_apply", 0, 400_000)]
           + [(k, s + MS, e + MS) for k, s, e in lm_modules(3, 1700, 104_000, 256 * 4_200)])
    jobs = [lm_job(1.7 * i, DEEPSEEK_NODE) for i in range(3)]
    material = window(jobs, lm_prompt("deepseek-v2-ep4-5l"))
    assert device_reader("prefill_device_ms.lm")(material) == pytest.approx(104.0)
    assert device_reader("decode_device_ms_per_token.lm")(material) == pytest.approx(4.20)
    cfg = deepseek_counts.config()
    # 1,536 pairs on held experts over 256 steps and 4 expert layers: 1.5 a step and layer
    step_bytes = deepseek_counts.decode_step_bytes(cfg, 1.5, 2048 + 128)
    assert step_bytes == pytest.approx(2.8116e9, rel=1e-4)
    by_hand = 100.0 * step_bytes / 0.00420 / 819e9
    assert by_hand == pytest.approx(81.74, abs=0.01)
    assert device_reader("decode_hbm_roofline_pct.lm")(material) == pytest.approx(by_hand)
    flops = deepseek_counts.prefill_flops(cfg, 2048, 12000)
    assert device_reader("prefill_mxu_peak_pct.lm")(material) == pytest.approx(
        100.0 * flops / 0.104 / 197e12)
    # the node's spans are the window's; a window whose node said nothing reads no share
    silent = window([txt2img_job(0.0)], lm_prompt("deepseek-v2-ep4-5l"))
    assert device_reader("decode_hbm_roofline_pct.lm")(silent) is None
    assert device_reader("decode_device_ms_per_token.lm")(silent) is None
    assert device_reader("prefill_device_ms.lm")(silent) == pytest.approx(104.0)


def test_device_the_lm_readers_find_a_models_work_by_the_checkpoint_the_workflow_loads(tmp_path, monkeypatch):
    traced = tracing(tmp_path, monkeypatch)
    traced([("jit__clip_apply", 0, 400_000)]
           + [(k, s + MS, e + MS) for k, s, e in lm_modules(3, 3300, 354_000, 64 * 38_400)])
    jobs = [lm_job(3.3 * i, OURO_NODE) for i in range(3)]
    material = window(jobs, lm_prompt("ouro-2.6b"))
    cfg = ouro_counts.config()
    assert device_reader("decode_hbm_roofline_pct.lm")(material) == pytest.approx(
        100.0 * ouro_counts.decode_step_bytes(cfg, 2048 + 32) / 0.0384 / 819e9)
    assert 73.0 < device_reader("decode_hbm_roofline_pct.lm")(material) < 75.0
    assert device_reader("prefill_mxu_peak_pct.lm")(material) == pytest.approx(
        100.0 * ouro_counts.prefill_flops(cfg, 2048) / 0.354 / 197e12)
    # every configuration with an lm_work file is found by its registry name, no table
    stems = {os.path.splitext(f)[0] for f in os.listdir(os.path.join(HERE, "lm_work"))
             if f.endswith(".py")}
    assert stems == {"deepseek-v2", "ouro-2.6b"}
    for stem in stems:
        with open(os.path.join(HERE, "configs", stem + ".json"), encoding="utf-8") as fh:
            name = json.load(fh)["registry_name"]
        work, found = device_modules.lm_work({"prompt": lm_prompt(name)})
        assert found["registry_name"] == name and callable(work)
    # a model with no such file: the device seconds still read, the shares do not
    unknown = window(jobs, lm_prompt("some-other-lm"))
    assert device_modules.lm_work(unknown) is None
    assert device_modules.lm_work({"prompt": lm_prompt("sd15")}) is None
    assert device_reader("decode_hbm_roofline_pct.lm")(unknown) is None
    assert device_reader("prefill_mxu_peak_pct.lm")(unknown) is None
    assert device_reader("decode_device_ms_per_token.lm")(unknown) == pytest.approx(38.4)


def test_device_mfu_is_model_operations_over_the_sampler_programs_device_seconds(tmp_path, monkeypatch):
    traced = tracing(tmp_path, monkeypatch)
    traced(txt2img_modules(4, 3000, sampler=2550, vae=200))
    jobs = [txt2img_job(3.0 * i, evals=20) for i in range(3)]
    cfg = flux_counts.config()
    flops = 20 * flux_counts.evaluation_flops(cfg, 4608)
    # 313 TFLOP of linears and modulations and the attention kernel's 78
    assert flops == pytest.approx(391.7e12, rel=1e-3)
    assert device_reader("mfu_pct.flux")(window(jobs)) == pytest.approx(
        100.0 * flops / (2.55 - 1e-6) / 197e12)
    no_evals = [txt2img_job(0.0, evals=None)]
    assert device_reader("mfu_pct.flux")(window(no_evals)) is None


def test_device_the_spans_own_idle_share_and_gap_serve_the_by_hand_timeline():
    # 425 ms of device work every 440 ms, as the host saw it, on the tracer's clock
    jobs = [txt2img_job(0.440 * i) for i in range(4)]
    material = window(list(reversed(jobs)))  # whatever order the traces came in
    assert device_spans.between_jobs_ms(material) == pytest.approx(20.0)
    assert device_spans.idle_pct(material) == pytest.approx(
        100.0 * (1 - 4 * 0.420 / (3 * 0.440 + 0.420)))
    assert device_spans.between_jobs_ms(window(jobs[:1])) is None
    assert device_spans.idle_pct(window(jobs[:1])) == pytest.approx(0.0)
    assert device_spans.idle_pct(window([])) is None


def test_device_gaps_go_to_the_innermost_executor_span_that_covers_them():
    first, second = txt2img_job(0.0), txt2img_job(0.440)
    second += [host_span("executor.between_jobs", 0.434, 0.4395, parent_id=None, idle=0),
               host_span("prompt_queue.wait", 0.300, 0.4395, parent_id=None)]
    table = device_timeline.gap_table({"a": first, "b": second})
    # the device ends at 0.425; SaveImage returns at 0.433; the thread is back at 0.434
    assert table == pytest.approx({
        "device.wait": 0.006, "node.SaveImage": 0.002, "execute_prompt": 0.001 + 0.0005 + 0.001,
        "executor.between_jobs": 0.0055, "node.KSampler": 0.004})
    assert sum(table.values()) == pytest.approx(0.020)
    assert device_timeline.by_program({"a": first, "b": second}) == pytest.approx(
        {"sampler": 0.800, "vae_decode": 0.040})
    assert device_timeline.gap_table({}) == {}
    assert device_timeline.after_ready_ms({"a": first, "b": second}) == pytest.approx(
        {"node.SaveImage": 6.0})


def test_device_lateness_is_the_annotations_end_after_the_last_program_ended():
    ms = 1_000_000
    module_ends = [100 * ms, 500 * ms, 520 * ms]
    watched = [(100 * ms + 40_000, "text_encode"), (500 * ms + 90_000, "sampler"),
               (520 * ms + 250_000, "vae_decode"), (50 * ms, "before_any")]
    assert device_timeline.lateness_ms(watched, module_ends) == pytest.approx([0.04, 0.09, 0.25])
