"""CPU rehearsal of chip_smoke.py: its phase functions at the `tiny` preset
through the same entry points (JaxTrainer, serve.run + streamed requests),
and the script itself refusing to produce a result without a chip."""

import os
import subprocess
import sys

import pytest

import ray_tpu
from ray_tpu import serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def cluster():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


@pytest.mark.parametrize("devices", [1, 4])
def test_train_phase_rehearsal(cluster, tmp_path, devices):
    """devices=4 is the four-chip layout (fsdp=2 x tp=2, the flash kernel
    under shard_map) on virtual CPU devices."""
    r = chip_smoke.train_phase("tiny", batch=4, seq=32, steps=5,
                               devices=devices, use_tpu=False,
                               out_dir=str(tmp_path), lr=1e-2)
    assert r["device"]["platform"] == "cpu"
    assert r["device"]["device_count"] == devices
    assert len(r["param_bytes_by_device"]) == devices
    assert r["losses"][-1] < r["losses"][0] and not r["pallas_in_step"]
    assert r["mesh"] == chip_smoke.MESHES[devices]


def test_serve_phase_rehearsal_and_infeasible_tpu_requests(cluster):
    """The waves follow chip_smoke.WAVES' rule: 44 and 76 leave the same
    12-token suffix past their last full 16-token page, so wave 2's hit (44)
    compiles the one suffix bucket (`suffix_prefill`, `install_kv`: two
    programs) and wave 3, wave 2 again, compiles no prefill.  What either
    may add is the first-token sample of a tick that admits two for the
    first time (`LLMEngine._sample_batch`'s eager `concatenate` and
    `_argmax` at that batch: two programs, once a process, in whichever
    wave the arrivals first fall into one tick), so wave 3 asks the compile
    cache for 0 or 2 programs and wave 2 for 2 or 4."""
    r = chip_smoke.serve_phase(
        "tiny", num_tpus=0, num_replicas=1, max_len=128, max_batch=4,
        waves=((44, 52, 100), (44, 76), (44, 76)), max_tokens=8)
    assert r["requests"] == 7 and r["tokens_out"] == 7 * 8
    assert r["max_active"] >= 2 and r["prefix_cache_hits"] >= 1
    assert r["waves"][2]["compiles"] <= 2 <= r["waves"][1]["compiles"]
    assert r["replicas"][0]["leased_chips"] == []
    # A TPU request on a cluster with no TPU fails as infeasible at once
    # (not after a placement-group or actor-scheduling timeout).
    from ray_tpu.llm.serve_patterns import build_llm_app
    from ray_tpu.train import JaxTrainer, ScalingConfig
    with pytest.raises(RuntimeError, match="infeasible"):
        JaxTrainer(chip_smoke.train_loop, scaling_config=ScalingConfig(
            num_workers=1, use_tpu=True)).fit()
    with pytest.raises(RuntimeError, match="infeasible"):
        serve.run(build_llm_app("tiny", num_tpus=1, min_replicas=1,
                                max_replicas=1))


def _run_script(tmp_path, **env):
    """`python chip_smoke.py` as the driver's harness runs it: no chip, a
    file-size limit (`ulimit -f`) in force.  Returns the finished process
    and the pids still alive that it started, directly or not."""
    import resource
    import uuid
    mark = f"CHIP_SMOKE_TEST_{uuid.uuid4().hex}"
    limit = 64 << 20
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"),
         "--out", str(tmp_path)], cwd=ROOT, capture_output=True, text=True,
        timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu", **env,
                              **{mark: "1"}),
        preexec_fn=lambda: resource.setrlimit(resource.RLIMIT_FSIZE,
                                              (limit, limit)))
    left = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/environ", "rb") as f:
                if mark.encode() in f.read():
                    left.append(int(pid))
        except OSError:
            pass
    return p, left


def test_script_exits_nonzero_without_a_chip(tmp_path):
    """The runtime starts under the file-size limit (the arena, one
    /dev/shm file, is sized to fit it), finds no chip, and says so."""
    p, left = _run_script(tmp_path)
    assert p.returncode != 0
    assert "found 0 TPU chip(s)" in p.stderr
    assert "to fit this process's file-size limit" in p.stderr
    assert '"ok"' not in p.stdout
    assert not left


def test_script_leaves_no_process_when_start_up_fails(tmp_path):
    """Chip discovery raises after the GCS has started: init() stops what
    it started, and the script ends with no result and no process."""
    p, left = _run_script(tmp_path, TPU_VISIBLE_CHIPS="zero")
    assert p.returncode != 0 and '"ok"' not in p.stdout
    assert "invalid literal" in p.stderr
    assert not left


def test_daemon_that_dies_before_ready_is_reported_with_its_stderr(tmp_path):
    from ray_tpu._private import node
    proc = node._spawn([sys.executable, "-c",
                        "import sys; sys.exit('arena: no room')"],
                       str(tmp_path), "doomed")
    with pytest.raises(RuntimeError, match="code 1 before ready.*\\n"
                                           "arena: no room"):
        node._wait_ready(str(tmp_path / "never.json"), proc)


def test_stop_descendants_kills_an_orphaned_grandchild():
    """A worker that outlives its agent is re-parented to the script (a
    subreaper), found there, and killed after the grace period."""
    code = f"""
import os, subprocess, sys
sys.path.insert(0, {ROOT!r})
import chip_smoke
chip_smoke._adopt_orphans()
p = subprocess.Popen(["sh", "-c", "sleep 300 >/dev/null & echo $!"],
                     stdout=subprocess.PIPE, text=True)
orphan = int(p.stdout.readline())
p.wait()
killed = chip_smoke._stop_descendants(grace_s=0.3)
assert killed == [orphan], (killed, orphan)
assert not os.path.exists(f"/proc/{{orphan}}")
"""
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


def test_auto_sized_arena_fits_the_file_size_limit(monkeypatch):
    import resource

    from ray_tpu._private import shm_store, worker
    from ray_tpu._private.config import Config
    free = worker._auto_store_bytes(Config(None))
    limit = free // 2 + shm_store.ARENA_OVERHEAD_BYTES
    monkeypatch.setattr(shm_store.resource, "getrlimit",
                        lambda which: (limit, resource.RLIM_INFINITY))
    assert shm_store.arena_bytes_limit() == free // 2
    assert worker._auto_store_bytes(Config(None)) == free // 2
