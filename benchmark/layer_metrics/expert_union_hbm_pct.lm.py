"""The expert kernel of a decode by masked diffusion against the HBM's
peak, in per cent:

    bytes a decode    = `decode_experts_read` (node.TextGenerate: the distinct experts the
                        passes' kernel calls read, a pass's block of positions sharing one
                        read of an expert two of them chose) x one expert's two matrices
    kernel s a decode = device seconds of the events named `expert_matvec` that begin
                        inside a run of `jit_decode` lying whole in the traced slice,
                        over the number of such runs
    share             = 100 x bytes a decode / kernel s a decode / peak bytes/s

with one expert's bytes from sdar_counts (9,437,184 at the published
widths in bfloat16) and the peak that of the chip the configuration
names. A pass multiplies 32 rows with the union of four positions'
choices, some 29 experts of 128 a layer, and every fetched block with all
32 rows: far left of the ridge still, so the bound is the HBM's. A
reading above 100 is a bug in the count. `expert_matvec_hbm_pct.lm` is
the same reading of Nemotron-3-Nano's cell and is held to that
configuration by its own text; the events' seconds are read by its
`kernel_seconds`.

Left out where the trace has no such kernel inside such a program, the
node says nothing of the experts read, or the workflow loads another
model."""

import importlib.util
import os
import statistics

import deepseek_reduce
import device_modules
import sdar_counts
import spans
import xplane


def _kernel_reader():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "expert_matvec_hbm_pct.lm.py")
    spec = importlib.util.spec_from_file_location("expert_matvec_hbm_pct_lm", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read(material):
    cfg = sdar_counts.config()
    found = device_modules.lm_work(material)  # the configuration of the model the workflow loads
    theirs = _kernel_reader()
    runs = [(start, end) for name, start, end in device_modules.modules(material)[1:-1]
            if name == theirs.MODULE]
    read_a_request = spans.per_request(
        material, lambda request: deepseek_reduce.attrs_of(request).get("decode_experts_read"))
    if (found is None or found[1]["registry_name"] != cfg["registry_name"] or not runs
            or not read_a_request):
        return None
    path = xplane.find_trace(device_modules.profile_dir())
    seconds = theirs.kernel_seconds(path, runs) / len(runs)
    if not seconds:
        return None
    fetched = statistics.median(read_a_request) * sdar_counts.expert_matrices_bytes(cfg)
    peak = sdar_counts.peaks(cfg["as_run"]["chip"])["bytes_per_s"]
    return 100.0 * fetched / seconds / peak
