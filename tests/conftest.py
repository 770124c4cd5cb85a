"""Shared fixtures (reference: python/ray/tests/conftest.py —
ray_start_regular :596, ray_start_cluster :686).

JAX tests run on a virtual 8-device CPU mesh so multi-chip sharding logic is
exercised without TPU hardware (the driver separately dry-runs the multichip
path via __graft_entry__.dryrun_multichip).
"""

import os

# The suite runs sharding logic on a virtual 8-device CPU mesh, pinned before
# any backend initialises (the chip is exercised separately, by
# chip_smoke.py through the chip tool — never from pytest).
if os.environ.get("RAY_TPU_TEST_PLATFORM", "cpu") == "cpu":
    flag = "--xla_force_host_platform_device_count=8"
    if flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    # Persistent compilation cache: the model/collective tests recompile
    # identical jaxprs every run (the suite's biggest wall-time sink on
    # small hosts); cache them across tests AND runs.  The variable is
    # exported so CPU artefacts stay out of the checkout (which the chip
    # tool copies) and so workers spawned by the runtime inherit it.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          "/tmp/ray_tpu_jax_cache")
    import jax
    try:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", 8)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    except RuntimeError:
        # Backend already initialized (e.g. a plugin touched
        # jax.devices()) — tests needing the 8-device mesh fail loudly
        # instead of the whole session aborting at collection.
        pass
    from ray_tpu._private.compile_cache import enable_compile_cache
    enable_compile_cache()

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running (multi-GiB data plane etc.); tier-1 runs "
        "with -m 'not slow'")
    config.addinivalue_line(
        "markers",
        "native_framer: needs the _rpcframe.so C extension; skipped "
        "(never a collection failure) when no compiler can build it")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection tests (process kills / RPC drops / link "
        "latency+partitions); guarded by a per-test wall-clock watchdog "
        "(RAY_TPU_CHAOS_WATCHDOG_S, default 180) that dumps every "
        "thread/task stack and fails the test instead of hanging; the "
        "long soaks are additionally marked slow — run them with "
        "-m 'chaos and slow'")
    config.addinivalue_line(
        "markers",
        "soak: many-node control-plane soak (simulated node fleets "
        "registering/heartbeating/reporting against one GCS, no real "
        "workers); the 100-node smoke runs in tier-1 (~30s), the "
        "500-node version is additionally marked slow — run it with "
        "-m 'soak and slow'")
    config.addinivalue_line(
        "markers",
        "serving: LLM serving subsystem (continuous batching, token "
        "streaming, prefix cache, queue-driven autoscaling); the "
        "tier-1 open-loop load test stays under ~60s on a tiny "
        "TransformerConfig, CPU devices")
    config.addinivalue_line(
        "markers",
        "dag: compiled actor pipelines (aDAG) over mutable shm "
        "channels — same-node futex rings, agent-bridged cross-node "
        "edges, channel-lowered collectives, typed failure semantics")
    config.addinivalue_line(
        "markers",
        "device_channel: device-direct data plane — DeviceArraySpec "
        "payloads over compiled-DAG edges (rung-0 same-process token "
        "handoff / rung-1 single-copy staging), the copy audit, "
        "device-tier replica-directory locations; CPU-safe on the "
        "forced-host-device mesh")
    config.addinivalue_line(
        "markers",
        "sp: long-context engine — sequence-parallel prefill attention "
        "(ring/Ulysses over the forced-host-device mesh) + cross-host "
        "paged KV; the multi-actor pool-exceeding serve test and the "
        "KV-host-loss chaos test are additionally marked slow so "
        "tier-1 keeps completing inside its budget")
    # Build the native RPC framer ONCE at session start so worker/agent
    # processes spawned by cluster fixtures just dlopen the committed or
    # freshly-built .so instead of racing g++ builds.  Failure is fine:
    # the runtime falls back to the pure-Python framer and the tests
    # marked native_framer skip themselves.
    try:
        from ray_tpu._private import rpcframe
        rpcframe.ensure_built()
    except Exception:
        pass


_FRAMER_PARITY_MODULES = ("test_data_plane", "test_replica_plane",
                          "test_submit_batching")


def pytest_generate_tests(metafunc):
    """Framer parity harness (opt-in, RAY_TPU_FRAMER_PARITY=1): run the
    data-plane, replica-plane and submit-batching suites under BOTH
    rpc_native_framer modes.  Off by default — the doubled runtime does
    not fit the tier-1 budget; tier-1 covers the native default plus the
    dedicated parity/fallback tests in test_rpc_framer.py.

    framer_parity_mode is AUTOUSE (so it is always in fixturenames —
    injecting names here is not supported on modern pytest) and a no-op
    unless this hook parametrizes it."""
    if not os.environ.get("RAY_TPU_FRAMER_PARITY"):
        return
    mod = metafunc.module.__name__.rsplit(".", 1)[-1]
    if mod not in _FRAMER_PARITY_MODULES:
        return
    metafunc.parametrize("framer_parity_mode", ["native", "python"],
                         indirect=True)


@pytest.fixture(autouse=True)
def framer_parity_mode(request):
    """Force the RPC framer mode for one test (driver process +
    RAY_TPU_rpc_native_framer env inherited by every daemon the test's
    cluster fixture spawns).  Unparametrized (the default, parity
    harness off) it does nothing."""
    mode = getattr(request, "param", None)
    if mode is None:
        yield None
        return
    from ray_tpu._private import rpc as rpc_mod
    prev_env = os.environ.get("RAY_TPU_rpc_native_framer")
    os.environ["RAY_TPU_rpc_native_framer"] = \
        "1" if mode == "native" else "0"
    rpc_mod.enable_native_framer(mode == "native")
    # A shared cluster initialized by an EARLIER test keeps its daemons'
    # (and the driver connections') original framer mode — tear it down
    # so this test's cluster fixture re-inits under the forced mode
    # (parity must reach the whole cluster, not just new connections).
    import ray_tpu as _rt
    if _rt.is_initialized():
        _rt.shutdown()
    try:
        yield mode
    finally:
        rpc_mod.enable_native_framer(None)
        if prev_env is None:
            os.environ.pop("RAY_TPU_rpc_native_framer", None)
        else:
            os.environ["RAY_TPU_rpc_native_framer"] = prev_env


class ChaosWatchdogTimeout(BaseException):
    """Raised INTO the test's main thread when the chaos watchdog fires.

    A BaseException so an `except Exception` inside the runtime or the
    test body can't swallow it before pytest reports the failure."""


def _dump_all_stacks(reason: str):
    """Every thread's frame (faulthandler) plus every asyncio task of the
    runtime's loop — the hang's exact shape, in the test log."""
    import faulthandler
    import sys
    sys.stderr.write(f"\n=== chaos watchdog: {reason} ===\n")
    sys.stderr.flush()
    faulthandler.dump_traceback(all_threads=True)
    try:
        import asyncio
        from ray_tpu._private import worker as worker_mod
        rt = worker_mod.global_runtime()
        loop = rt.core.loop if rt is not None else None
        if loop is not None and loop.is_running():
            for task in asyncio.all_tasks(loop):
                task.print_stack(file=sys.stderr)
    except Exception:
        pass  # best-effort: thread stacks above are the load-bearing part
    sys.stderr.flush()


@pytest.fixture(autouse=True)
def _chaos_watchdog(request):
    """Wall-clock watchdog for chaos-marked tests: a regression that
    reintroduces a hang (the failure mode this suite exists to prevent)
    shows up as a stack trace within minutes instead of eating the whole
    tier-1 budget.  On expiry: dump all stacks, raise
    ChaosWatchdogTimeout in the test's thread, and — if the test is so
    wedged it can't even take an async exception (blocked in C) —
    hard-exit after a grace period, pytest-timeout style."""
    if request.node.get_closest_marker("chaos") is None:
        yield
        return
    budget = float(os.environ.get("RAY_TPU_CHAOS_WATCHDOG_S", "180"))
    if budget <= 0:
        yield
        return
    import ctypes
    import threading
    main_tid = threading.get_ident()
    done = threading.Event()

    def _expire():
        if done.wait(budget):
            return
        _dump_all_stacks(
            f"{request.node.nodeid} still running after {budget:.0f}s")
        if done.is_set():
            # The test finished while we were dumping stacks: an async
            # exception now would land in teardown or the NEXT test.
            return
        ctypes.pythonapi.PyThreadState_SetAsyncExc(
            ctypes.c_ulong(main_tid),
            ctypes.py_object(ChaosWatchdogTimeout))
        if not done.wait(15.0):
            # Blocked in a C call that never returns: the async exception
            # can't land.  Ending the run with a clear verdict beats
            # silently burning the remaining suite budget.
            import sys
            sys.stderr.write("=== chaos watchdog: test unkillable, "
                             "aborting run ===\n")
            sys.stderr.flush()
            os._exit(70)

    guard = threading.Thread(target=_expire, name="chaos-watchdog",
                             daemon=True)
    guard.start()
    try:
        yield
    finally:
        done.set()
        guard.join(timeout=5.0)


@pytest.fixture(autouse=True)
def _collect_previous_test_garbage():
    """pytest machinery keeps the previous test's frame reachable into
    the next test; actors whose handles live in that frame then hold
    their CPUs. Collecting up front releases them before this test
    competes for resources."""
    import gc
    gc.collect()
    yield


@pytest.fixture
def captured_recorder():
    """A context manager that swaps in a flight recorder whose rows the
    driver's telemetry flush cannot steal (a live shared cluster drains the
    process singleton every second: mid-test, during a multi-second first
    compile, in a loaded run): `drain()`, the telemetry entry point, yields
    nothing; the test reads `rows()`."""
    from contextlib import contextmanager

    from ray_tpu._private import flight_recorder

    class _Cap(flight_recorder.FlightRecorder):
        def drain(self, node_id=b"", worker_id=b""):
            return []

        def rows(self):
            return flight_recorder.FlightRecorder.drain(self)

    @contextmanager
    def swap():
        old = flight_recorder._recorder
        cap = flight_recorder._recorder = _Cap()
        try:
            yield cap
        finally:
            flight_recorder._recorder = old
    return swap


@pytest.fixture
def ray_start_regular():
    """Shared cluster: initialized on first use, reused across tests, torn
    down at interpreter exit (isolated-fixture tests shut it down and the
    next user re-initializes)."""
    import ray_tpu
    if not ray_tpu.is_initialized():
        ray_tpu.init(num_cpus=4)
    yield


@pytest.fixture
def ray_start_isolated():
    """Fresh cluster per test (slower; for failure-injection tests)."""
    import ray_tpu
    ray_tpu.init(num_cpus=4)
    yield
    ray_tpu.shutdown()


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    import jax
    devs = jax.devices("cpu")
    assert len(devs) >= 8, f"expected 8 virtual cpu devices, got {len(devs)}"
    return devs
