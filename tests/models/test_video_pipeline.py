"""Video pipeline: t2v shapes/determinism, seed-parallel over the mesh,
and the video workflow through the graph executor."""

import numpy as np

from comfyui_distributed_tpu.graph import ExecutionContext, GraphExecutor
from comfyui_distributed_tpu.models import video_pipeline as vp
from comfyui_distributed_tpu.parallel import build_mesh
from comfyui_distributed_tpu.parallel.collective import host_collect


def test_t2v_shapes_and_determinism():
    bundle = vp.load_video_pipeline("tiny-dit", seed=0)
    out = vp.t2v(bundle, "a river", frames=4, height=32, width=32, steps=2, seed=3)
    assert out.shape == (1, 4, 32, 32, 3)
    arr = np.asarray(out)
    assert np.isfinite(arr).all() and (arr >= 0).all() and (arr <= 1).all()
    again = vp.t2v(bundle, "a river", frames=4, height=32, width=32, steps=2, seed=3)
    np.testing.assert_array_equal(arr, np.asarray(again))


def test_t2v_parallel_participant_major():
    bundle = vp.load_video_pipeline("tiny-dit", seed=0)
    mesh = build_mesh({"data": 8})
    out = vp.t2v_parallel(
        bundle, mesh, "a storm", frames=4, height=32, width=32, steps=2, seed=9
    )
    vids = host_collect(out)
    assert vids.shape == (8, 4, 32, 32, 3)
    assert len({vids[i].tobytes() for i in range(8)}) == 8


def test_video_workflow_in_graph():
    prompt = {
        "1": {"class_type": "VideoCheckpointLoader", "inputs": {"ckpt_name": "tiny-dit"}},
        "2": {"class_type": "VideoCLIPTextEncode", "inputs": {"text": "waves", "clip": ["1", 1]}},
        "3": {"class_type": "VideoCLIPTextEncode", "inputs": {"text": "", "clip": ["1", 1]}},
        "4": {"class_type": "EmptyVideoLatent", "inputs": {"width": 32, "height": 32, "frames": 4}},
        "5": {"class_type": "DistributedSeed", "inputs": {"seed": 1}},
        "6": {
            "class_type": "VideoFlowSampler",
            "inputs": {
                "model": ["1", 0], "seed": ["5", 0], "steps": 2, "cfg": 2.0,
                "positive": ["2", 0], "negative": ["3", 0], "latent": ["4", 0],
            },
        },
        "7": {"class_type": "DistributedCollector", "inputs": {"images": ["6", 0]}},
        "8": {"class_type": "PreviewImage", "inputs": {"images": ["7", 0]}},
    }
    ctx = ExecutionContext(mesh=build_mesh({"data": 8}))
    outputs = GraphExecutor(ctx).execute(prompt)
    images = np.asarray(list(outputs.values())[0][0]["images"])
    # 8 participants x 4 frames, flattened to an IMAGE batch
    assert images.shape == (32, 32, 32, 3)


def test_i2v_clamps_first_frame():
    bundle = vp.load_video_pipeline("tiny-dit", seed=0)
    img = np.random.default_rng(4).random((1, 32, 32, 3)).astype(np.float32)
    out = vp.i2v(bundle, vp.jnp.asarray(img), "pan right", frames=4, steps=2, seed=1)
    assert out.shape == (1, 4, 32, 32, 3)
    arr = np.asarray(out)
    assert np.isfinite(arr).all()
    # frame 0 decodes the clamped reference latent: it must be much
    # closer to the VAE round-trip of the input than later frames are
    ref_rt = np.asarray(
        vp.decode_frames(bundle, vp.encode_frames(bundle, vp.jnp.asarray(img)[:, None]))
    )[0, 0]
    d0 = np.abs(arr[0, 0] - ref_rt).mean()
    d3 = np.abs(arr[0, 3] - ref_rt).mean()
    assert d0 < d3


def test_multihost_noop_without_config(monkeypatch):
    from comfyui_distributed_tpu.parallel import multihost

    for var in ("CDT_COORDINATOR", "CDT_NUM_PROCESSES", "CDT_PROCESS_ID", "CDT_MULTIHOST"):
        monkeypatch.delenv(var, raising=False)
    assert multihost.maybe_init_multihost() is False
    assert multihost.is_multihost() is False
