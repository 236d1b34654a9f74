"""Serving control plane (`h2o_tpu/serving/control.py` + `router.py`):
placement + admission quotas, replica dispatch, weighted/canary routing.

The load-bearing pins:

- **routing determinism**: the weighted split is a pure function of
  (seed, request ordinal) — a fixed seed replays the exact variant
  sequence, and over >=10k requests the canary serves its configured
  share within binomial tolerance.
- **shadow bit-parity**: shadow variants see IDENTICAL rows, the response
  comes only from the serving variant (bit-equal to scoring it directly),
  and divergence stats populate the route surface.
- **quota isolation**: an over-quota registration (or a placement OOM —
  the `serving.place` failpoint) is a typed 429 + Retry-After while
  co-registered models keep scoring untouched; cold placements evict
  under pressure and lazily re-place on first hit.
- **replica dispatch**: N replicas land on distinct CPU-mesh devices,
  submits spread least-loaded by live queue depth, and a failpoint-killed
  replica is marked dead with every affected request transparently
  re-dispatched — zero failures, zero requests routed to it after
  detection.
- **pooled wire**: the client reuses one persistent connection per
  thread, survives a server restart via the stale-socket redial, and
  `H2O_TPU_CLIENT_KEEPALIVE=0` reverts to per-request connections.
"""

import threading
import time

import numpy as np
import pytest

import h2o_tpu.api as h2o
from h2o_tpu.backend import memory
from h2o_tpu.frame.frame import Frame
from h2o_tpu.frame.vec import T_CAT, Vec
from h2o_tpu.serving import (AdmissionError, QueueFullError,
                             RouteNotFoundError, ServingRuntime,
                             estimate_model_bytes)
from h2o_tpu.serving.router import Route, Variant, _unit
from h2o_tpu.utils import failpoints, telemetry

pytestmark = pytest.mark.serving

BUCKETS = [1, 8, 64]


def _training_frames():
    rng = np.random.default_rng(7)
    n = 300
    x1 = rng.normal(size=n).astype(np.float32)
    logits = x1 * 1.5
    lab = (rng.random(n) < 1 / (1 + np.exp(-logits))).astype(np.float32)
    binom = Frame(["x1", "y"],
                  [Vec.from_numpy(x1),
                   Vec.from_numpy(lab, type=T_CAT, domain=["no", "yes"])])
    yreg = (logits + rng.normal(scale=0.1, size=n)).astype(np.float32)
    reg = Frame(["x1", "y"], [Vec.from_numpy(x1), Vec.from_numpy(yreg)])
    return binom, reg


@pytest.fixture(scope="module")
def models():
    from h2o_tpu.models.gbm import GBM, GBMParameters
    from h2o_tpu.models.glm import GLM, GLMParameters

    binom, reg = _training_frames()
    champ = GBM(GBMParameters(training_frame=binom, response_column="y",
                              ntrees=8, max_depth=3, seed=1)).train_model()
    canary = GBM(GBMParameters(training_frame=binom, response_column="y",
                               ntrees=4, max_depth=2, seed=2)).train_model()
    glm = GLM(GLMParameters(training_frame=reg, response_column="y",
                            family="gaussian", seed=1)).train_model()
    return {"champ": champ, "canary": canary, "glm": glm}


def _rows(n, seed=0):
    rng = np.random.default_rng(seed)
    return [{"x1": float(v)} for v in rng.normal(size=n)]


@pytest.fixture()
def runtime(models):
    rt = ServingRuntime()
    yield rt
    rt.shutdown()
    failpoints.reset()


# ---------------------------------------------------------------------------
# routing determinism + canary split
# ---------------------------------------------------------------------------
def test_split_unit_deterministic_and_uniform():
    """The split hash is a pure function of (seed, ordinal) and close to
    uniform — the property every split guarantee rests on."""
    a = [_unit(42, i) for i in range(1000)]
    b = [_unit(42, i) for i in range(1000)]
    assert a == b
    c = [_unit(43, i) for i in range(1000)]
    assert a != c
    assert 0.4 < float(np.mean(a)) < 0.6
    assert all(0.0 <= u < 1.0 for u in a)


def test_fixed_seed_exact_split_counts():
    """Two routes with the same seed pick the IDENTICAL variant sequence;
    a different seed picks a different one."""

    def mk(seed):
        return Route("ep", [Variant("a", 0.7, False),
                            Variant("b", 0.3, False)], seed)

    r1, r2, r3 = mk(7), mk(7), mk(8)
    seq1 = [r1.pick()[0].model_id for _ in range(2000)]
    seq2 = [r2.pick()[0].model_id for _ in range(2000)]
    seq3 = [r3.pick()[0].model_id for _ in range(2000)]
    assert seq1 == seq2                    # fixed seed -> exact replay
    assert seq1 != seq3
    # and the counts are exactly reproducible run-to-run by construction
    assert seq1.count("a") + seq1.count("b") == 2000


def test_canary_split_binomial_tolerance_10k():
    """A 1% canary over >=10k requests serves within 5 sigma of its
    weight (sigma = sqrt(n p (1-p)) ~ 10 at n=10000, p=0.01)."""
    route = Route("ep", [Variant("champ", 0.99, False),
                         Variant("canary", 0.01, False)], seed=42)
    n = 10_000
    picks = [route.pick()[0].model_id for _ in range(n)]
    canary = picks.count("canary")
    sigma = (n * 0.01 * 0.99) ** 0.5
    assert abs(canary - n * 0.01) < 5 * sigma
    assert route.stats()["requests"] == n


def test_route_rejects_shadow_only_and_unknown_models(runtime, models):
    runtime.register_model(models["champ"], "champ",
                           overrides={"buckets": [1, 8]})
    with pytest.raises(ValueError):
        runtime.router.create_route(
            "ep", [{"model_id": "champ", "shadow": True}])
    with pytest.raises(KeyError):
        runtime.router.create_route(
            "ep", [{"model_id": "ghost", "weight": 1.0}])
    with pytest.raises(RouteNotFoundError):
        runtime.router.score("ghost-ep", _rows(1))


# ---------------------------------------------------------------------------
# shadow traffic: bit-parity + divergence
# ---------------------------------------------------------------------------
def test_shadow_bit_parity_and_divergence(runtime, models):
    """The canary shadow sees IDENTICAL rows; the response comes only from
    the primary — bit-equal to scoring the primary directly — and the
    divergence window fills with |prediction deltas|."""
    runtime.register_model(models["champ"], "champ",
                           overrides={"buckets": BUCKETS})
    runtime.register_model(models["canary"], "canary",
                           overrides={"buckets": BUCKETS})
    runtime.router.create_route(
        "main", [{"model_id": "champ", "weight": 1.0},
                 {"model_id": "canary", "shadow": True}], seed=5)
    rows = _rows(37, seed=3)
    direct = runtime.score("champ", rows)
    routed, served_by = runtime.router.score("main", rows)
    assert served_by == "champ"
    assert routed == direct        # dict equality == float bit equality
    assert runtime.router.drain_shadow()
    st = runtime.router.stats("main")
    shadow = next(v for v in st["variants"] if v["shadow"])
    assert shadow["shadow_rows"] == len(rows)   # identical rows, all seen
    assert shadow["requests"] == 0              # never served a response
    div = shadow["divergence"]
    assert div is not None and div["window"] == len(rows)
    assert div["max"] >= div["p50"] >= 0.0
    # the deltas are REAL: canary is a different forest, so shadow scoring
    # of the same rows must differ somewhere
    assert div["max"] > 0.0


def test_shadow_master_switch(runtime, models, monkeypatch):
    runtime.register_model(models["champ"], "champ",
                           overrides={"buckets": [1, 8]})
    runtime.register_model(models["canary"], "canary",
                           overrides={"buckets": [1, 8]})
    runtime.router.create_route(
        "main", [{"model_id": "champ", "weight": 1.0},
                 {"model_id": "canary", "shadow": True}])
    monkeypatch.setenv("H2O_TPU_SERVING_SHADOW", "0")
    runtime.router.score("main", _rows(5))
    assert runtime.router.drain_shadow()
    st = runtime.router.stats("main")
    assert next(v for v in st["variants"] if v["shadow"])["shadow_rows"] == 0


def test_weighted_routing_end_to_end(runtime, models):
    """Both variants actually serve traffic at a 50/50 split through the
    real scoring path, and per-variant serve counts add up."""
    runtime.register_model(models["champ"], "champ",
                           overrides={"buckets": BUCKETS})
    runtime.register_model(models["canary"], "canary",
                           overrides={"buckets": BUCKETS})
    runtime.router.create_route(
        "ab", [{"model_id": "champ", "weight": 0.5},
               {"model_id": "canary", "weight": 0.5}], seed=9)
    n = 60
    for i in range(n):
        preds, mid = runtime.router.score("ab", [_rows(1, seed=i)[0]])
        assert len(preds) == 1 and mid in ("champ", "canary")
    st = runtime.router.stats("ab")
    counts = {v["model_id"]: v["requests"] for v in st["variants"]}
    assert counts["champ"] + counts["canary"] == n
    assert counts["champ"] > 0 and counts["canary"] > 0


def test_zero_steady_state_compiles_through_router(runtime, models):
    """The PR 4 invariant survives the control plane: routed traffic —
    weighted picks, replica dispatch, shadow scoring — never compiles
    after registration warmed every bucket."""
    from h2o_tpu.utils import compilemeter

    runtime.register_model(models["champ"], "champ",
                           overrides={"buckets": BUCKETS})
    runtime.register_model(models["canary"], "canary",
                           overrides={"buckets": BUCKETS, "replicas": 2})
    runtime.router.create_route(
        "main", [{"model_id": "champ", "weight": 0.5},
                 {"model_id": "canary", "weight": 0.5},
                 {"model_id": "canary", "shadow": True}], seed=3)
    for i in range(4):                      # prime both variants + shadow
        runtime.router.score("main", _rows(3, seed=i))
    assert runtime.router.drain_shadow()
    before = compilemeter.count()
    for i in range(20):
        runtime.router.score("main", _rows(1 + i % 9, seed=100 + i))
    assert runtime.router.drain_shadow()
    assert compilemeter.count() - before == 0
    assert runtime.stats("champ")["recompiles"] == 0
    assert runtime.stats("canary")["recompiles"] == 0


# ---------------------------------------------------------------------------
# placement + admission quotas
# ---------------------------------------------------------------------------
def _quota_env(monkeypatch, budget_bytes, fraction="0.5"):
    monkeypatch.setenv("H2O_TPU_HBM_LIMIT_BYTES", str(int(budget_bytes)))
    monkeypatch.setenv("H2O_TPU_SERVING_QUOTA_FRACTION", fraction)


def test_cost_estimate_scales_with_replicas(models):
    one = estimate_model_bytes(models["champ"], [1, 8], 1, replicas=1)
    three = estimate_model_bytes(models["champ"], [1, 8], 1, replicas=3)
    assert one > 0 and three == 3 * one


def test_over_quota_429_isolation(runtime, models, monkeypatch):
    """Model B registers and keeps scoring; model A is refused with the
    typed AdmissionError (429 semantics) — and B never notices."""
    cost_b = estimate_model_bytes(models["glm"], [1, 8], 1)
    # quota fits B plus slack, but not B + A (A is the bigger forest)
    _quota_env(monkeypatch, (cost_b + 2048) * 2, fraction="0.5")
    runtime.register_model(models["glm"], "model_b",
                           overrides={"buckets": [1, 8]})
    before = runtime.score("model_b", _rows(3))
    with pytest.raises(AdmissionError) as ei:
        runtime.register_model(models["champ"], "model_a",
                               overrides={"buckets": BUCKETS})
    assert ei.value.retry_after_s > 0
    assert ei.value.budget_bytes > 0
    # isolation: B is untouched — still placed, still scoring, bit-equal
    assert runtime.score("model_b", _rows(3)) == before
    assert runtime.control.placement("model_b").placed
    assert runtime.control.placement("model_a") is None
    snap = runtime.control_snapshot()
    assert snap["placements"]["model_b"]["placed"]


def test_placement_oom_failpoint_is_admission_error(runtime, models,
                                                    monkeypatch):
    """`serving.place` armed raise(oom): the placement-OOM path surfaces
    as the SAME typed 429 — and a co-registered model keeps scoring."""
    runtime.register_model(models["glm"], "model_b",
                           overrides={"buckets": [1, 8]})
    # armed AFTER model_b placed: the NEXT admit is hit 1 under this spec
    failpoints.arm("serving.place", "raise(oom)@1")
    with pytest.raises(AdmissionError):
        runtime.register_model(models["champ"], "model_a",
                               overrides={"buckets": [1, 8]})
    failpoints.disarm("serving.place")
    assert len(runtime.score("model_b", _rows(2))) == 2
    assert "model_a" not in runtime.model_ids()
    # nothing leaked: the failed registration left no placement behind
    assert runtime.control.placement("model_a") is None


def test_cold_evicted_then_lazily_replaced(runtime, models, monkeypatch):
    """A cold placement yields to a hot registration under quota pressure
    (executables dropped, reservation released) and re-places itself on
    first hit once the pressure clears — predictions bit-equal across the
    evict/re-place cycle."""
    cost_cold = estimate_model_bytes(models["glm"], [1, 8], 1)
    cost_hot = estimate_model_bytes(models["champ"], [1, 8], 1)
    # quota fits EITHER model (plus half the cold's bytes of slack) but
    # never both — the hot registration must push the cold one out
    _quota_env(monkeypatch,
               (max(cost_cold, cost_hot) + cost_cold // 2) * 2,
               fraction="0.5")
    runtime.register_model(models["glm"], "cold_m",
                           overrides={"buckets": [1, 8],
                                      "priority": "cold"})
    before = runtime.score("cold_m", _rows(4))
    evict_ctr = telemetry.value("serving.placement.evicted.count")
    runtime.register_model(models["champ"], "hot_m",
                           overrides={"buckets": [1, 8]})
    pl = runtime.control.placement("cold_m")
    assert pl is not None and not pl.placed and pl.evictions == 1
    assert not runtime.model("cold_m").scorer.placed   # executables gone
    assert telemetry.value("serving.placement.evicted.count") == \
        evict_ctr + 1
    # quota still full: the lazy re-place on first hit is itself refused
    with pytest.raises(AdmissionError):
        runtime.score("cold_m", _rows(2))
    # pressure clears -> first hit re-places and scores bit-equal
    runtime.unregister("hot_m")
    assert runtime.score("cold_m", _rows(4)) == before
    assert runtime.control.placement("cold_m").placed
    assert runtime.model("cold_m").scorer.placed


def test_failed_reregistration_keeps_prior_placement(runtime, models,
                                                     monkeypatch):
    """A rejected RE-registration must not strip the still-serving prior
    registration of its placement or reservation (review catch: release()
    in the failure path destroyed the survivor's accounting)."""
    cost = estimate_model_bytes(models["glm"], [1, 8], 1)
    _quota_env(monkeypatch, cost * 4, fraction="0.5")   # fits 1x, not 4x
    runtime.register_model(models["glm"], "m", overrides={"buckets": [1, 8]})
    before = runtime.score("m", _rows(3))
    reserved = memory.reserved_bytes()
    with pytest.raises(AdmissionError):
        runtime.register_model(models["glm"], "m",
                               overrides={"buckets": [1, 8],
                                          "replicas": 8})
    pl = runtime.control.placement("m")
    assert pl is not None and pl.placed and pl.cost_bytes == cost
    assert memory.reserved_bytes() == reserved          # ledger intact
    assert runtime.score("m", _rows(3)) == before       # still serving


def test_route_rejects_invalid_weights(runtime, models):
    runtime.register_model(models["glm"], "m", overrides={"buckets": [1, 8]})
    for bad in (-0.5, float("nan"), float("inf")):
        with pytest.raises(ValueError):
            runtime.router.create_route(
                "ep", [{"model_id": "m", "weight": 1.0},
                       {"model_id": "m", "weight": bad}])


def test_prometheus_label_escaping(models):
    from h2o_tpu.serving import get_runtime
    from h2o_tpu.serving.runtime import _prometheus_model_lines

    rt = get_runtime()
    rt.register_model(models["glm"], 'we"ird\\id',
                      overrides={"buckets": [1, 8]})
    try:
        lines = _prometheus_model_lines()
        joined = "\n".join(lines)
        assert r'model="we\"ird\\id"' in joined
    finally:
        rt.unregister('we"ird\\id')


def test_hot_never_evicted(runtime, models, monkeypatch):
    cost = estimate_model_bytes(models["glm"], [1, 8], 1)
    _quota_env(monkeypatch, (cost + 2048) * 2, fraction="0.5")
    runtime.register_model(models["glm"], "hot_a",
                           overrides={"buckets": [1, 8]})
    with pytest.raises(AdmissionError):
        runtime.register_model(models["champ"], "hot_b",
                               overrides={"buckets": [1, 8]})
    assert runtime.control.placement("hot_a").placed


def test_reservations_debit_shared_budget(runtime, models, monkeypatch):
    """Placed serving bytes show up in the ONE shared accounting: the
    Cleaner's sweep threshold and the planner budget both shrink."""
    monkeypatch.setenv("H2O_TPU_HBM_LIMIT_BYTES", str(64 << 20))
    base_limit = memory.CLEANER.limit_bytes()
    base_budget = memory.hbm_budget_bytes()
    runtime.register_model(models["glm"], "resv",
                           overrides={"buckets": [1, 8]})
    cost = runtime.control.placement("resv").cost_bytes
    assert cost > 0
    # the placement debits the Cleaner's sweep threshold by exactly its
    # cost (delta assertion: other fixtures' leftover reservations cancel)
    assert base_limit - memory.CLEANER.limit_bytes() == cost
    assert memory.hbm_budget_bytes() == base_budget  # env pin is exact
    runtime.unregister("resv")
    assert memory.CLEANER.limit_bytes() == base_limit  # released on unreg


# ---------------------------------------------------------------------------
# replica scorers
# ---------------------------------------------------------------------------
def test_replicas_on_distinct_devices(runtime, models):
    info = runtime.register_model(models["glm"], "rep",
                                  overrides={"buckets": [1, 8],
                                             "replicas": 3})
    devices = [r["device"] for r in info["replicas"]]
    assert len(devices) == 3 and len(set(devices)) == 3  # >=2-device mesh
    # replicated scoring is bit-equal to a single-replica registration
    runtime.register_model(models["glm"], "single",
                           overrides={"buckets": [1, 8]})
    rows = _rows(13, seed=4)
    assert runtime.score("rep", rows) == runtime.score("single", rows)


def test_replica_least_loaded_dispatch(runtime, models):
    """With every batcher paused, concurrent submits spread across the
    replicas by live queue depth — no lane hogs the traffic."""
    runtime.register_model(models["glm"], "rep",
                           overrides={"buckets": [1, 8], "replicas": 3,
                                      "deadline_ms": 0})
    served = runtime.model("rep")
    served.replicas.pause()
    threads = [threading.Thread(
        target=lambda i=i: runtime.score("rep", [_rows(1, seed=i)[0]]),
        daemon=True) for i in range(6)]
    try:
        for t in threads:
            t.start()
        deadline = time.time() + 5
        while served.depth < 6 and time.time() < deadline:
            time.sleep(0.005)
        depths = sorted(r.batcher.depth for r in served.replicas.replicas)
        assert depths == [2, 2, 2]          # least-loaded: perfectly even
    finally:
        served.replicas.resume()
        for t in threads:
            t.join(timeout=10)
    assert served.stats.snapshot()["requests"] == 6


def test_replica_death_drains_and_reroutes(runtime, models):
    """serving.replica raise@1 kills the replica executing the first
    batch: the affected request is transparently re-dispatched (zero
    failures), the replica is marked dead, and dispatch never picks it
    again."""
    runtime.register_model(models["glm"], "rep",
                           overrides={"buckets": [1, 8], "replicas": 2})
    served = runtime.model("rep")
    dead_before = telemetry.value("serving.replica.dead.count")
    failpoints.arm("serving.replica", "raise@1")
    rows = _rows(3, seed=1)
    out = runtime.score("rep", rows)        # batch 1 dies -> rerouted
    assert len(out) == 3                    # ZERO failed requests
    dead = [r for r in served.replicas.replicas if r.dead]
    assert len(dead) == 1
    assert telemetry.value("serving.replica.dead.count") == dead_before + 1
    assert telemetry.value("serving.replica.reroute.count") >= 1
    # after detection, the dead replica is never picked again
    for i in range(8):
        runtime.score("rep", [_rows(1, seed=i)[0]])
        assert served.replicas.pick().idx != dead[0].idx
    snap = served.stats.snapshot()
    assert snap["requests"] == 9
    # the healthy replica serves bit-equal to a fresh registration
    runtime.register_model(models["glm"], "oracle",
                           overrides={"buckets": [1, 8]})
    assert runtime.score("rep", rows) == runtime.score("oracle", rows)


def test_all_replicas_dead_is_typed(runtime, models):
    from h2o_tpu.serving import ServingShutdownError

    runtime.register_model(models["glm"], "rep1",
                           overrides={"buckets": [1, 8]})
    served = runtime.model("rep1")
    failpoints.arm("serving.replica", "raise")      # every call dies
    with pytest.raises(Exception) as ei:
        runtime.score("rep1", _rows(2))
    assert isinstance(ei.value, (ServingShutdownError,
                                 failpoints.InjectedFault))
    failpoints.disarm("serving.replica")


# ---------------------------------------------------------------------------
# over-rate isolation (queue-full on A never touches B)
# ---------------------------------------------------------------------------
def test_queue_full_isolation_across_models(runtime, models):
    runtime.register_model(models["glm"], "sat",
                           overrides={"buckets": [1, 8], "queue_depth": 1,
                                      "deadline_ms": 0})
    runtime.register_model(models["champ"], "calm",
                           overrides={"buckets": [1, 8]})
    sat = runtime.model("sat")
    sat.replicas.pause()
    try:
        t = threading.Thread(
            target=lambda: runtime.score("sat", _rows(1)), daemon=True)
        t.start()
        deadline = time.time() + 5
        while sat.depth < 1 and time.time() < deadline:
            time.sleep(0.005)
        with pytest.raises(QueueFullError):
            runtime.score("sat", _rows(1, seed=2))
        # model B keeps scoring while A is saturated
        assert len(runtime.score("calm", _rows(3))) == 3
    finally:
        sat.replicas.resume()
        t.join(timeout=10)


# ---------------------------------------------------------------------------
# REST + client surface (routes, admission, control, pooled wire)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def cloud(worker_port):
    conn = h2o.init(port=worker_port(54643))
    yield conn
    try:
        h2o.shutdown()
    except Exception:
        pass


@pytest.fixture()
def rest_models(cloud, models):
    from h2o_tpu.serving import get_runtime

    rt = get_runtime()
    h2o.register_serving(models["champ"].key, serving_id="champ",
                         buckets="1,8")
    h2o.register_serving(models["canary"].key, serving_id="canary",
                         buckets="1,8")
    yield rt
    for ep in list(rt.router.endpoints()):
        rt.router.delete_route(ep)
    for sid in ("champ", "canary"):
        try:
            h2o.unregister_serving(sid)
        except Exception:
            pass


def test_rest_route_lifecycle(cloud, rest_models):
    r = h2o.create_route("main", [
        {"model_id": "champ", "weight": 0.95},
        {"model_id": "canary", "weight": 0.05},
        {"model_id": "canary", "shadow": True}], seed=7)
    assert r["endpoint"] == "main" and r["seed"] == 7
    preds = h2o.route_score("main", _rows(6, seed=2))
    assert len(preds) == 6
    rest_models.router.drain_shadow()
    st = h2o.route_stats("main")
    assert st["requests"] == 1
    shadow = next(v for v in st["variants"] if v["shadow"])
    assert shadow["shadow_rows"] == 6
    assert shadow["divergence"] is not None
    listing = h2o.route_stats()
    assert any(rr["endpoint"] == "main" for rr in listing["routes"])
    ctrl = h2o.serving_control()
    assert "main" in ctrl["routes"] and ctrl["placed_bytes"] > 0
    assert h2o.delete_route("main")["deleted"]
    with pytest.raises(h2o.H2OConnectionError) as ei:
        h2o.route_score("main", _rows(1))
    assert ei.value.status == 404


def test_rest_route_validation(cloud, rest_models):
    with pytest.raises(h2o.H2OConnectionError) as ei:
        h2o.create_route("bad", [{"model_id": "ghost", "weight": 1.0}])
    assert ei.value.status == 404
    with pytest.raises(h2o.H2OConnectionError) as ei:
        h2o.create_route("bad", [{"model_id": "champ", "shadow": True}])
    assert ei.value.status == 400


def test_rest_admission_429_with_retry_after(cloud, rest_models, models,
                                             monkeypatch):
    cost = estimate_model_bytes(models["glm"], [1, 8], 1)
    monkeypatch.setenv("H2O_TPU_HBM_LIMIT_BYTES", str(cost * 2))
    monkeypatch.setenv("H2O_TPU_SERVING_QUOTA_FRACTION", "0.0001")
    with pytest.raises(h2o.H2OConnectionError) as ei:
        h2o.register_serving(models["glm"].key, serving_id="crowded",
                             buckets="1,8")
    assert ei.value.status == 429
    assert int(ei.value.headers.get("Retry-After")) >= 1
    assert ei.value.payload["error_type"] == "admission_rejected"
    # isolation over the wire too: the registered fleet still scores
    assert len(h2o.score_rows("champ", _rows(2))) == 2


def test_rest_register_with_priority_and_replicas(cloud, rest_models,
                                                  models):
    reg = h2o.register_serving(models["glm"].key, serving_id="repl",
                               buckets="1,8", replicas=2, priority="cold")
    try:
        assert len(reg["replicas"]) == 2
        assert reg["placement"]["priority"] == "cold"
        assert reg["placement"]["cost_bytes"] > 0
        assert len(h2o.score_rows("repl", _rows(3))) == 3
    finally:
        h2o.unregister_serving("repl")


def test_per_model_prometheus_labels(cloud, rest_models):
    h2o.score_rows("champ", _rows(2))
    text = cloud.request("GET", "/3/Metrics",
                         params={"format": "prometheus"}, raw=True)
    assert 'h2o_tpu_serving_model_requests{model="champ"}' in text
    assert 'h2o_tpu_serving_model_queue_depth{model="canary"}' in text
    # the fleet-total families are still there, label-free
    assert "\nh2o_tpu_serving_request_count " in text


def test_pooled_wire_reuses_connection(cloud):
    cloud.request("GET", "/3/About")
    conn1 = cloud._pool.conn
    assert conn1 is not None
    for _ in range(3):
        cloud.request("GET", "/3/About")
    assert cloud._pool.conn is conn1          # same keep-alive connection
    assert conn1.sock is not None


def test_pooled_wire_keepalive_off_reverts(cloud, monkeypatch):
    monkeypatch.setenv("H2O_TPU_CLIENT_KEEPALIVE", "0")
    cloud._pool.conn = None
    cloud.request("GET", "/3/About")
    assert getattr(cloud._pool, "conn", None) is None  # nothing pooled


def test_pooled_wire_redials_stale_socket(cloud):
    """Kill the pooled socket under the client (the server-restart /
    keep-alive-timeout shape) — the next request redials transparently,
    with the outer retry policy disabled so the redial itself is pinned."""
    cloud.request("GET", "/3/About")
    stale = cloud._pool.conn
    assert stale is not None
    stale.sock.close()     # half-dead socket: send/recv now fail
    out = cloud.request("GET", "/3/About", retry=False)
    assert out["entries"]
    assert cloud._pool.conn is not None


def test_wire_upload_still_streams(cloud, tmp_path):
    """The pooled wire preserves the file-upload path (Content-Length
    set, body streamed) — PostFile round-trips."""
    p = tmp_path / "up.csv"
    p.write_text("a,b\n1,2\n3,4\n")
    fr = h2o.upload_file(str(p))
    assert fr.nrow == 2 and fr.ncol == 2
    h2o.remove(fr)
