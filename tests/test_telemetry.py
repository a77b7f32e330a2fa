"""The closed-loop autotune subsystem (`repro.telemetry`), end to end.

Layers under test: the bounded observation ring (hot-path collection +
JSONL export), the Eq.-5 dataset reconstruction from totals-only telemetry,
the Eq.-2-shaped :class:`LatencyModel`, the gated deterministic
:class:`OnlineRefitter` (injectable clock, min-sample and staleness
thresholds, fp-determinism), and the session acceptance contract: with
``autotune="live"`` seeded observations accumulate, the refit fires and the
session's chunk picks become the refit heuristic's, while ``"shadow"``
leaves picks untouched and ``"off"`` records nothing. Observations are
*synthetic* (crafted via the public ``TelemetryBuffer.record``) wherever a
fit is asserted on, so every assertion is deterministic.
"""

import json
import math

import numpy as np
import pytest

from repro.core.tridiag import ensure_x64

ensure_x64()

from repro.api import (  # noqa: E402
    AUTOTUNE_MODES,
    BatchObservation,
    LatencyModel,
    OnlineRefitter,
    SolveRequest,
    SolverConfig,
    TelemetryBuffer,
    TridiagSession,
)
from repro.core.autotune.heuristic import fit_stream_heuristic  # noqa: E402
from repro.core.streams.simulator import StreamSimulator  # noqa: E402
from repro.core.streams.timemodel import (  # noqa: E402
    overhead_from_measurement,
)
from repro.core.tridiag.plan import price_chunks  # noqa: E402
from repro.core.tridiag.reference import (  # noqa: E402
    make_diag_dominant_system,
)
from repro.telemetry.refit import (  # noqa: E402
    DEFAULT_OVERLAP_FRACTION,
    dataset_from_observations,
)


def obs(size, k, latency_ms, *, t=0.0, batch=1, predicted=None):
    """One synthetic same-size observation (batch systems of ``size``)."""
    return BatchObservation(
        t=t,
        sizes=(size,) * batch,
        num_chunks=k,
        backend="reference",
        layout="system-major",
        latency_ms=latency_ms,
        mean_wait_ms=0.1,
        max_wait_ms=0.2,
        predicted_ms=predicted,
    )


def streams_help_observations(
    sizes=(2000, 4000, 8000, 16000), ks=(1, 2, 4, 8), reps=3
):
    """A synthetic machine where chunking clearly pays.

    Serial latency ``t_non = 1e-3·n`` ms, half of it overlappable; k chunks
    recover ``(k-1)/k`` of the overlappable half minus a small
    log-in-k overhead — so the Eq.-6 gain grows with k at every size and a
    refit heuristic must pick k > 1.
    """
    out = []
    t = 0.0
    for n in sizes:
        t_non = 1e-3 * n
        s = 0.5 * t_non
        for k in ks:
            if k == 1:
                lat = t_non
            else:
                L = math.log2(k)
                lat = t_non - (k - 1) / k * s + 0.02 * L + 0.005 * L * L
            for _ in range(reps):
                out.append(obs(n, k, lat, t=t))
                t += 0.01
    return out


# ------------------------------------------------------------------- ring --
def test_ring_bounds_window_and_counts_drops():
    buf = TelemetryBuffer(capacity=4)
    for i in range(6):
        assert buf.record(obs(100, 1, 1.0, t=float(i)))
    assert len(buf) == 4
    snap = buf.snapshot()
    # Oldest two fell off the far end, newest four remain in order.
    assert [o.t for o in snap] == [2.0, 3.0, 4.0, 5.0]
    assert buf.counters() == {"recorded": 6, "dropped": 2, "buffered": 4}


def test_ring_capacity_zero_disables_collection():
    buf = TelemetryBuffer(capacity=0)
    assert not buf.enabled
    assert buf.record(obs(100, 1, 1.0)) is False
    assert buf.counters() == {"recorded": 0, "dropped": 0, "buffered": 0}
    with pytest.raises(ValueError, match="capacity"):
        TelemetryBuffer(capacity=-1)


def test_ring_clear_keeps_lifetime_counters():
    buf = TelemetryBuffer(capacity=8)
    for i in range(3):
        buf.record(obs(100, 1, 1.0))
    assert buf.clear() == 3
    assert len(buf) == 0
    assert buf.counters()["recorded"] == 3


def test_ring_jsonl_roundtrip(tmp_path):
    buf = TelemetryBuffer(capacity=8)
    buf.record(obs(200, 4, 2.5, t=1.0, batch=2, predicted=2.0))
    buf.record(obs(100, 1, 1.25, t=2.0))
    path = tmp_path / "observations.jsonl"
    assert buf.export_jsonl(str(path)) == 2
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    assert len(rows) == 2
    assert rows[0]["sizes"] == [200, 200]
    assert rows[0]["batch"] == 2
    assert rows[0]["effective_size"] == 400
    assert rows[0]["num_chunks"] == 4
    assert rows[0]["predicted_ms"] == 2.0
    assert rows[0]["residual_ms"] == pytest.approx(0.5)
    assert rows[1]["predicted_ms"] is None
    assert rows[1]["residual_ms"] is None
    assert buf.to_jsonl().splitlines() == path.read_text().splitlines()


# ---------------------------------------------------------- latency model --
def test_latency_model_recovers_planted_coefficients():
    rng = np.random.default_rng(0)
    n = rng.integers(100, 10_000, size=64).astype(float)
    k = rng.choice([1, 2, 4, 8], size=64).astype(float)
    y = 0.5 + 1e-3 * n + 0.2 * n / k
    model = LatencyModel.fit(n, k, y)
    assert model.samples == 64
    assert model.coef == pytest.approx((0.5, 1e-3, 0.2), abs=1e-9)
    assert model.predict_ms(1000, 4) == pytest.approx(0.5 + 1.0 + 50.0)
    # Determinism: same observations, bit-identical coefficients.
    again = LatencyModel.fit(n, k, y)
    assert again.coef == model.coef
    # Predictions are clamped non-negative.
    flat = LatencyModel(coef=(-5.0, 0.0, 0.0))
    assert flat.predict_ms(10, 1) == 0.0


def test_latency_model_needs_observations():
    with pytest.raises(ValueError, match="at least one observation"):
        LatencyModel.fit([], [], [])


# -------------------------------------------------- dataset reconstruction --
def test_dataset_reconstruction_matches_eq5():
    observations = streams_help_observations()
    data = dataset_from_observations(observations)
    assert data is not None
    # One row per (size, k>1) cell that has a serial baseline.
    assert len(data) == 4 * 3
    by_cell = {(r["size"], r["num_str"]): r for r in data.rows}
    t_non = 1e-3 * 2000
    row = by_cell[(2000, 4)]
    assert row["t_non_str"] == pytest.approx(t_non)
    assert row["sum"] == pytest.approx(DEFAULT_OVERLAP_FRACTION * t_non)
    assert row["t_overhead"] == pytest.approx(
        overhead_from_measurement(row["t_str"], row["t_non_str"], row["sum"], 4)
    )


def test_dataset_skips_sizes_without_serial_baseline():
    observations = streams_help_observations(sizes=(2000, 4000))
    # A size observed only at k > 1 contributes no rows (no Eq.-5 baseline).
    observations += [obs(64_000, 2, 30.0), obs(64_000, 4, 20.0)]
    data = dataset_from_observations(observations)
    assert data is not None
    assert {r["size"] for r in data.rows} == {2000, 4000}


def test_dataset_none_when_structurally_thin():
    # One size only — can't fit the Eq.-4 size axis.
    assert dataset_from_observations(streams_help_observations(sizes=(2000,))) is None
    # One chunk level only — can't fit the Eq.-7 num_str axis.
    assert (
        dataset_from_observations(streams_help_observations(ks=(1, 2))) is None
    )
    assert dataset_from_observations([]) is None


# ---------------------------------------------------------------- refitter --
class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def test_refitter_gates_on_samples_and_staleness():
    clock = FakeClock()
    r = OnlineRefitter(
        "shadow", min_samples=8, interval_s=10.0, clock=clock
    )
    buf = TelemetryBuffer(capacity=64)
    for o in streams_help_observations(reps=1)[:4]:
        buf.record(o)
    # Below min_samples: not due, and no sleep hint either.
    assert not r.due(len(buf))
    assert r.seconds_until_due(len(buf)) is None
    assert r.maybe_refit(buf) is None
    for o in streams_help_observations(reps=1):
        buf.record(o)
    # Enough samples, never attempted: due immediately.
    assert r.due(len(buf))
    assert r.seconds_until_due(len(buf)) == 0.0
    assert r.maybe_refit(buf) is not None
    # Freshly attempted: not due again until interval_s passes.
    assert not r.due(len(buf))
    assert r.seconds_until_due(len(buf)) == pytest.approx(10.0)
    clock.t = 9.9
    assert not r.due(len(buf))
    clock.t = 10.0
    assert r.due(len(buf))


def test_refitter_failed_attempt_resets_staleness():
    # A structurally-thin window (single size) refits to nothing — but the
    # attempt still consumes the staleness budget, so the idle worker can't
    # busy-loop retrying it.
    clock = FakeClock()
    r = OnlineRefitter("shadow", min_samples=2, interval_s=5.0, clock=clock)
    buf = TelemetryBuffer(capacity=64)
    for o in streams_help_observations(sizes=(2000,), reps=1):
        buf.record(o)
    result = r.maybe_refit(buf)
    assert result is not None and result.heuristic is None
    assert not r.due(len(buf))
    stats = r.stats_snapshot()
    assert stats["refit_attempts"] == 1 and stats["refits"] == 0


def test_refit_is_deterministic_and_stamps_provenance():
    r = OnlineRefitter("live", min_samples=1)
    observations = streams_help_observations()
    a = r.refit_from(observations)
    b = r.refit_from(list(observations))
    assert a.heuristic is not None and b.heuristic is not None
    assert (
        a.heuristic.base.sum_model.coef == b.heuristic.base.sum_model.coef
    )
    assert np.array_equal(a.heuristic.base.popt_small, b.heuristic.base.popt_small)
    assert a.latency_model.coef == b.latency_model.coef
    assert a.heuristic.provenance["source"] == "refit"
    assert a.heuristic.provenance["samples"] == len(observations)
    # Live mode ships a ready-to-swap policy; shadow must not.
    assert a.policy is not None
    shadow = OnlineRefitter("shadow", min_samples=1).refit_from(observations)
    assert shadow.heuristic is not None and shadow.policy is None


def test_refit_off_mode_fits_only_the_latency_model():
    r = OnlineRefitter("off", min_samples=1)
    result = r.refit_from(streams_help_observations())
    assert result.heuristic is None and result.policy is None
    assert result.latency_model is not None


def test_offline_fit_provenance():
    sim = StreamSimulator()
    data = sim.dataset(sizes=(200_000, 400_000), reps=1)
    fitted = fit_stream_heuristic(data)
    assert fitted.provenance == {"source": "offline-fit", "samples": len(data)}


def test_refitter_rejects_bad_mode():
    assert AUTOTUNE_MODES == ("off", "shadow", "live")
    with pytest.raises(ValueError, match="mode"):
        OnlineRefitter("eager")


def test_refitter_agreement_counters():
    clock = FakeClock()
    r = OnlineRefitter("shadow", min_samples=1, interval_s=0.0, clock=clock)
    buf = TelemetryBuffer(capacity=256)
    for o in streams_help_observations():
        buf.record(o)
    # An active policy that always picks 1 must disagree with the refit
    # heuristic on every composition (streams clearly pay here).
    result = r.maybe_refit(buf, pick_active=lambda sizes: 1)
    assert result is not None and result.heuristic is not None
    assert result.agreement == 0.0
    stats = r.stats_snapshot()
    assert stats["pick_disagree"] > 0 and stats["pick_agree"] == 0
    assert stats["agreement_rate"] == 0.0
    # Agreeing with the refit picks itself scores 1.0.
    clock.t += 1.0
    heur = r.last_heuristic()
    result = r.maybe_refit(
        buf, pick_active=lambda sizes: price_chunks(heur, sizes)
    )
    assert result is not None and result.agreement == 1.0


# -------------------------------------------------- config + session wiring --
def test_config_validates_autotune_fields():
    with pytest.raises(ValueError, match="autotune"):
        SolverConfig(autotune="on").validate()
    with pytest.raises(ValueError, match="telemetry"):
        SolverConfig(autotune="live", telemetry_capacity=0).validate()
    with pytest.raises(ValueError, match="refit_min_samples"):
        SolverConfig(refit_min_samples=0).validate()
    with pytest.raises(ValueError, match="refit_interval_s"):
        SolverConfig(refit_interval_s=-1.0).validate()
    with pytest.raises(ValueError, match="max_predicted_ms"):
        SolverConfig(max_predicted_ms=0.0).validate()
    SolverConfig(autotune="shadow", max_predicted_ms=5.0).validate()


def _serve_some(session, n_requests=3, size=200):
    rng = np.random.default_rng(7)
    futs = []
    for i in range(n_requests):
        dl, d, du, b = make_diag_dominant_system(size, seed=i)[:4]
        futs.append(session.submit(SolveRequest(i, dl, d, du, b)))
    return [f.result(timeout=30) for f in futs]


def test_session_off_records_nothing():
    cfg = SolverConfig(m=10, max_wait_ms=1.0)
    with TridiagSession(cfg) as session:
        _serve_some(session)
        assert not session.telemetry.enabled
        assert len(session.telemetry) == 0
        stats = session.stats
    assert stats["autotune"]["mode"] == "off"
    assert stats["autotune"]["observations"] == {
        "recorded": 0,
        "dropped": 0,
        "buffered": 0,
    }


def test_session_records_observations_while_serving():
    cfg = SolverConfig(m=10, max_wait_ms=1.0, autotune="shadow")
    with TridiagSession(cfg) as session:
        _serve_some(session, n_requests=4)
        assert session.telemetry.enabled
        snap = session.telemetry.snapshot()
        assert len(snap) >= 1
        assert all(o.sizes and o.num_chunks >= 1 for o in snap)
        assert all(o.latency_ms > 0 for o in snap)
        assert session.stats["autotune"]["mode"] == "shadow"


def _seeded_session(mode, clock):
    cfg = SolverConfig(m=10, max_wait_ms=1.0, autotune=mode)
    # The fake clock never advances, so with a non-zero interval exactly one
    # refit fires (the test's own); the worker cannot refit again on the
    # served batches' observations and reprice them mid-test.
    refitter = OnlineRefitter(
        mode, min_samples=1, interval_s=1.0, clock=clock
    )
    session = TridiagSession(cfg, refitter=refitter)
    for o in streams_help_observations():
        session.telemetry.record(o)
    return session, refitter


def test_session_live_refit_swaps_chunk_policy():
    """The acceptance loop: seeded observations accumulate, the refit fires
    once due, and the session's picks become the refit heuristic's."""
    clock = FakeClock()
    session, refitter = _seeded_session("live", clock)
    with session:
        sizes = (2000, 2000)
        assert session.plan_for(sizes).num_chunks == 1  # config default
        session._maybe_refit()
        heur = refitter.last_heuristic()
        assert heur is not None
        expected = price_chunks(heur, sizes)
        assert expected > 1  # streams clearly pay on the synthetic machine
        assert session.plan_for(sizes).num_chunks == expected
        # ... and served batches are priced by the swapped policy too.
        _serve_some(session, n_requests=2, size=2000)
        stats = session.stats
        per_batch = stats["per_batch"]
        assert per_batch, "serving recorded no batches"
        for entry in per_batch:
            assert entry["num_chunks"] == price_chunks(
                heur, tuple(entry["sizes"])
            )
        assert stats["autotune"]["refits"] >= 1
        assert stats["autotune"]["last_refit_age_s"] is not None


def test_session_shadow_refit_leaves_picks_untouched():
    clock = FakeClock()
    session, refitter = _seeded_session("shadow", clock)
    with session:
        sizes = (2000, 2000)
        session._maybe_refit()
        assert refitter.last_heuristic() is not None
        # The shadow fit exists — and changed nothing.
        assert session.plan_for(sizes).num_chunks == 1
        _serve_some(session, n_requests=2, size=2000)
        stats = session.stats
        assert all(e["num_chunks"] == 1 for e in stats["per_batch"])
        assert stats["autotune"]["refits"] >= 1
        # The would-be picks disagree with the active (default) pricing.
        assert stats["autotune"]["pick_disagree"] > 0


def test_worker_fires_refit_on_its_own():
    """Driven through serving alone: enough real observations accumulate and
    the worker's idle loop runs the refit without any test intervention."""
    cfg = SolverConfig(
        m=10,
        max_wait_ms=1.0,
        autotune="shadow",
        refit_min_samples=1,
        refit_interval_s=0.0,
    )
    with TridiagSession(cfg) as session:
        _serve_some(session, n_requests=4)
        deadline = 5.0
        import time as _time

        t0 = _time.monotonic()
        while _time.monotonic() - t0 < deadline:
            if session.stats["autotune"]["refit_attempts"] >= 1:
                break
            _time.sleep(0.01)
        assert session.stats["autotune"]["refit_attempts"] >= 1


def test_refit_errors_are_counted_not_fatal(monkeypatch):
    clock = FakeClock()
    r = OnlineRefitter("live", min_samples=1, interval_s=0.0, clock=clock)
    buf = TelemetryBuffer(capacity=64)
    for o in streams_help_observations():
        buf.record(o)
    monkeypatch.setattr(
        r, "refit_from", lambda obs_: (_ for _ in ()).throw(RuntimeError("boom"))
    )
    assert r.maybe_refit(buf) is None
    stats = r.stats_snapshot()
    assert stats["refit_errors"] == 1 and stats["refits"] == 0
