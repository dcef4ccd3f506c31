"""Cluster layer: roofline-derived elasticity, cluster days, fault tolerance."""

import numpy as np
import pytest

from repro.cluster.elasticity import arch_elasticity, classify_elasticity, service_minutes
from repro.cluster.workload import ClusterWorkloadSpec, generate_cluster_jobs
from repro.core.jobs import ElasticityClass
from repro.core.metrics import et_table
from repro.core.simulator import StaticPolicy, queue_heuristic_policy
from repro.distributed.fault_tolerance import (
    FailureModel,
    HeartbeatMonitor,
    StragglerDetector,
)
from repro.launch.cluster_sim import run_days


def test_elasticity_curves_are_valid():
    for arch, shape in [
        ("gemma3-1b", "decode_32k"),
        ("nemotron-4-340b", "train_4k"),
        ("mixtral-8x7b", "decode_32k"),
        ("xlstm-350m", "long_500k"),
    ]:
        e = arch_elasticity(arch, shape)
        assert e.throughput(1) == pytest.approx(1.0, rel=1e-6)
        prev = 0.0
        for k in range(1, 8):
            tp = e.throughput(k)
            assert tp >= prev - 1e-9  # monotone
            assert tp <= k + 1e-9  # never superlinear
            prev = tp


def test_elasticity_classes_emerge_from_roofline():
    # batch-1 recurrent decode cannot scale -> capped, always
    assert arch_elasticity("xlstm-350m", "long_500k").klass == ElasticityClass.CAPPED
    # across the serving mix, at least two distinct classes must emerge
    # (which cell lands in which class depends on whether analytic or
    # compiled-artifact terms are available — e.g. compiled FSDP training
    # is collective-bound and degrades from linear to sublinear)
    classes = {
        arch_elasticity(a, s).klass
        for a, s in [
            ("nemotron-4-340b", "train_4k"),
            ("gemma3-12b", "train_4k"),
            ("mixtral-8x7b", "decode_32k"),
            ("xlstm-350m", "long_500k"),
            ("whisper-base", "decode_32k"),
        ]
    }
    assert len(classes) >= 2, classes


def test_service_minutes_monotone_in_slots():
    for arch, shape in [("gemma3-12b", "train_4k"), ("mixtral-8x7b", "decode_32k")]:
        ts = [service_minutes(arch, shape, k) for k in range(1, 8)]
        assert all(b <= a + 1e-9 for a, b in zip(ts, ts[1:], strict=False))


def test_cluster_jobs_generation():
    jobs = generate_cluster_jobs(ClusterWorkloadSpec(horizon_min=240.0), seed=0)
    assert len(jobs) > 10
    for j in jobs:
        assert j.work > 0 and j.deadline > j.arrival


@pytest.mark.slow
def test_dynamic_beats_static_on_cluster():
    per = {
        "static": run_days(lambda: StaticPolicy(3), iterations=3),
        "dyn": run_days(queue_heuristic_policy, iterations=3),
    }
    table, _ = et_table(per)
    assert table["dyn"] < table["static"]


@pytest.mark.slow
def test_failure_injection_degrades_but_completes():
    fm = FailureModel(mtbf_minutes=8 * 60.0, seed=3)
    ok = run_days(queue_heuristic_policy, iterations=2, seed=5)
    bad = run_days(queue_heuristic_policy, iterations=2, failures=fm, seed=5)
    assert all(r.num_jobs > 0 for r in bad)  # all days complete
    # failures cost tardiness (lost work + degraded config)
    assert sum(r.avg_tardiness for r in bad) >= sum(r.avg_tardiness for r in ok) - 1e-6


def test_failure_model_sampling():
    fm = FailureModel(mtbf_minutes=100.0, repair_minutes=10.0, seed=0)
    ev = fm.sample_failures(7, 1000.0)
    assert ev == sorted(ev)
    assert all(0 <= t < 1000.0 and r == t + 10.0 for t, _, r in ev)
    assert len(ev) > 10  # ~7 slices x 10 expected failures


def test_heartbeat_monitor():
    hb = HeartbeatMonitor(interval_min=1.0, misses_to_fail=3)
    hb.beat(0, t=0.0)
    hb.beat(1, t=0.0)
    assert hb.check(2.0) == []
    hb.beat(1, t=2.0)
    assert hb.check(3.5) == [0]  # slice 0 missed 3 intervals
    assert hb.check(3.6) == []  # reported once
    hb.beat(0, t=4.0)  # recovery
    assert 0 not in hb.failed


def test_straggler_detector():
    sd = StragglerDetector(straggler_factor=0.7, alpha=1.0)
    assert not sd.observe(0, observed_rate=1.0, nominal_rate=1.0)
    assert sd.observe(0, observed_rate=0.5, nominal_rate=1.0)
    sd.reset(0)
    assert not sd.observe(0, observed_rate=1.0, nominal_rate=1.0)
