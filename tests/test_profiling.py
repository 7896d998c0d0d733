"""Profiling: per-phase step timers in fit, XLA trace capture, and the
TrainSummary scalar plumbing (SURVEY §5.1 rebuild)."""

import glob
import os

import numpy as np

from zoo_tpu.common.profiling import PhaseTimer, StepProfiler, trace
from zoo_tpu.pipeline.api.keras.engine.topology import Sequential
from zoo_tpu.pipeline.api.keras.layers import Dense


def _model():
    m = Sequential(name="prof")
    m.add(Dense(16, activation="relu", input_shape=(8,)))
    m.add(Dense(1))
    m.compile(optimizer="sgd", loss="mse")
    return m


def _data(n=64):
    rng = np.random.RandomState(0)
    x = rng.randn(n, 8).astype(np.float32)
    return x, x[:, :1] * 2.0


def test_phase_timer_stats():
    t = PhaseTimer()
    for dt in (0.01, 0.03):
        t.record(dt)
    s = t.stats()
    assert s["count"] == 2
    assert abs(s["avg_ms"] - 20.0) < 1e-6
    assert abs(s["max_ms"] - 30.0) < 1e-6


def test_fit_records_phases():
    m = _model()
    prof = m.set_profile()
    x, y = _data()
    m.fit(x, y, batch_size=16, nb_epoch=2, verbose=0)
    # epoch_scalars resets per epoch; after fit the current-epoch stats
    # are drained, but the summary got the scalars
    steps = m.train_summary.read_scalar("StepTimeMs")
    waits = m.train_summary.read_scalar("DataTimeMs")
    assert len(steps) == 2 and len(waits) == 2
    assert all(v > 0 for _, v in steps)
    m.clear_profile()
    assert m.get_profile_stats() == {}
    assert prof is not None


def test_fit_without_profiler_unchanged():
    m = _model()
    x, y = _data()
    h = m.fit(x, y, batch_size=16, nb_epoch=1, verbose=0)
    assert len(h["loss"]) == 1
    assert m.get_profile_stats() == {}


def test_xla_trace_capture(tmp_path):
    m = _model()
    m.set_profile(trace_dir=str(tmp_path), trace_epochs=1)
    x, y = _data(32)
    m.fit(x, y, batch_size=16, nb_epoch=2, verbose=0)
    produced = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                         recursive=True)
    assert produced, "expected an XPlane trace under the profile dir"


def test_standalone_trace_window(tmp_path):
    m = _model()
    x, _ = _data(16)
    with trace(str(tmp_path)):
        m.predict(x, batch_size=16)
    produced = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                         recursive=True)
    assert produced


def test_profiler_via_estimator():
    from zoo_tpu.orca.learn.keras.estimator import Estimator
    m = _model()
    est = Estimator.from_keras(m)
    est.set_profile()
    x, y = _data()
    est.fit({"x": x, "y": y}, batch_size=16, epochs=1)
    assert "step" in est.get_profile_stats()


def test_eval_phase_and_save_strips_profiler(tmp_path):
    m = _model()
    m.set_profile()
    x, y = _data()
    m.fit(x, y, batch_size=16, nb_epoch=1, verbose=0,
          validation_data=(x[:16], y[:16]))
    assert "eval" in m.get_profile_stats()
    assert len(m.train_summary.read_scalar("EvalTimeMs")) == 1
    p = str(tmp_path / "m.zoo")
    m.save(p)
    from zoo_tpu.pipeline.api.keras.engine.topology import KerasNet
    loaded = KerasNet.load(p)
    assert getattr(loaded, "_profiler", None) is None
    assert m._profiler is not None  # original untouched

