"""The asynchronous runtime engine: units + end-to-end measured runs.

End-to-end runs use real threads and real coded matmuls, so they take a
few seconds each; delays are kept small but large enough to dominate the
per-round overhead (~1 ms).
"""

import threading

import numpy as np
import pytest

from repro.core import layering, simulator
from repro.runtime import (FusionNode, LayeredResult, Master, RoundFusion,
                           RuntimeConfig, StragglerModel, format_delay_table,
                           make_jobs, run_jobs)
from repro.runtime.metrics import RuntimeResult
from repro.runtime.tasks import RoundContext, TaskResult


def _result(job_id, round_idx, task_id, value, t=0.0):
    return TaskResult(job_id=job_id, round_idx=round_idx, task_id=task_id,
                      worker_id=0, value=value, finished_at=t)


class TestRoundFusion:
    def test_fuses_at_kth_result_and_drops_late(self):
        ctx = RoundContext(0, 0)
        rf = RoundFusion(ctx, k=3)
        for t in range(3):
            assert rf.post(_result(0, 0, t, np.full((2, 2), t), t=1.0 + t))
        assert rf.wait(timeout=0.0)
        assert rf.fused_at == 3.0                 # k-th arrival's clock
        assert not rf.post(_result(0, 0, 3, np.zeros((2, 2))))  # stale

    def test_purged_round_rejects_results(self):
        ctx = RoundContext(0, 0)
        rf = RoundFusion(ctx, k=2)
        ctx.purge()
        assert not rf.post(_result(0, 0, 0, np.zeros((2, 2))))
        assert not rf.wait(timeout=0.0)

    def test_decode_reconstructs_minijob(self, rng):
        cfg = RuntimeConfig(mu=(400.0, 500.0), omega=1.5)
        code = cfg.code()
        a = rng.integers(0, 255, size=(32, 8)).astype(np.float64)
        b = rng.integers(0, 255, size=(32, 8)).astype(np.float64)
        X, Y = code.encode(a, b)
        ctx = RoundContext(0, 0)
        rf = RoundFusion(ctx, k=code.k)
        # deliver an arbitrary k-subset, e.g. the last k codewords
        for t in range(code.num_tasks - code.k, code.num_tasks):
            rf.post(_result(0, 0, t, X[t].T @ Y[t]))
        np.testing.assert_allclose(rf.decode(code), a.T @ b,
                                   rtol=1e-9, atol=1e-6)

    def test_fusion_node_routes_and_counts_stale(self):
        node = FusionNode()
        ctx = RoundContext(job_id=1, round_idx=2)
        rf = node.begin_round(ctx, k=1)
        node.post(_result(9, 9, 0, np.zeros((1, 1))))   # wrong round
        assert node.stale_results == 1
        node.post(_result(1, 2, 0, np.zeros((1, 1))))
        assert rf.wait(timeout=0.0)


class TestLayeredResult:
    def test_best_resolution_scans_from_top(self):
        """MSB-first publishing means the first set event from the top is
        the answer; unset lower layers must not mask a ready higher one."""
        lr = LayeredResult(job_id=0, num_layers=4)
        lr.mark_resolution(0, np.zeros((1, 1)), t=0.0)
        lr.mark_resolution(1, np.ones((1, 1)), t=1.0)
        assert lr.best_resolution() == 1
        lr.mark_resolution(3, np.full((1, 1), 3.0), t=2.0)
        assert lr.best_resolution() == 3        # layer 2 still unset
        np.testing.assert_array_equal(lr.result(), np.full((1, 1), 3.0))

    def test_per_resolution_readiness_and_release(self):
        lr = LayeredResult(job_id=0, num_layers=3)
        assert lr.best_resolution() == -1
        with pytest.raises(RuntimeError):
            lr.result()
        lr.mark_resolution(0, np.ones((2, 2)), t=1.5)
        assert lr.resolution_ready(0) and not lr.resolution_ready(1)
        assert lr.best_resolution() == 0
        lr.release(terminated=True)
        assert lr.terminated and lr.released_resolution == 0
        np.testing.assert_array_equal(lr.result(), np.ones((2, 2)))

    def test_wait_resolution_unblocks_consumer(self):
        lr = LayeredResult(job_id=0, num_layers=2)
        seen = {}

        def consumer():
            lr.wait_resolution(0, timeout=5.0)
            seen["value"] = lr.resolution(0)

        th = threading.Thread(target=consumer)
        th.start()
        lr.mark_resolution(0, np.full((1,), 7.0), t=0.0)
        th.join(timeout=5.0)
        assert seen["value"][0] == 7.0


class TestStragglerModel:
    def _model(self, **kw):
        cfg = RuntimeConfig(mu=(400.0, 500.0, 600.0), **kw)
        return cfg, StragglerModel(cfg, np.random.default_rng(0))

    def test_none_injects_zero(self):
        _, sm = self._model(straggler="none")
        assert (sm.sample(0, 5) == 0).all()

    def test_exp_matches_simulator_scale(self):
        cfg, sm = self._model(straggler="exp", complexity=8.0)
        draws = sm.sample(1, 20000)
        want = cfg.minijob_complexity / cfg.mu[1]
        assert draws.mean() == pytest.approx(want, rel=0.05)

    def test_stall_pins_listed_workers(self):
        cfg, sm = self._model(straggler="stall", stall_workers=(2,),
                              stall_seconds=9.0)
        assert (sm.sample(2, 3) == 9.0).all()
        assert (sm.sample(0, 3) < 9.0).all()   # exp draws, not stalled

    def test_bad_model_rejected(self):
        with pytest.raises(ValueError):
            RuntimeConfig(mu=(1.0,), straggler="bogus")
        with pytest.raises(ValueError):
            RuntimeConfig(mu=(1.0,), stall_workers=(3,))


class TestConfig:
    def test_load_split_sums_to_total_tasks(self):
        cfg = RuntimeConfig(mu=(400.0, 650.0, 380.0), omega=1.5)
        kappa = cfg.load_split()
        assert kappa.sum() == cfg.total_tasks == 6
        assert cfg.k == 4 and cfg.num_layers == 3 and cfg.num_rounds == 4

    def test_to_system_config_roundtrip(self):
        cfg = RuntimeConfig(mu=(400.0, 500.0), arrival_rate=3.0,
                            complexity=7.0, omega=1.25, gamma=2.0)
        scfg = cfg.to_system_config()
        assert scfg.k == cfg.k and scfg.total_tasks == cfg.total_tasks
        assert scfg.m == cfg.m and scfg.mu == cfg.mu
        assert scfg.arrival_rate == cfg.arrival_rate


def test_make_jobs_matches_one_int64_draw():
    """Operands drawn a slab of rows at a time and stored as int16 hold the
    same values as one int64 draw per operand, in the same order."""
    cfg = RuntimeConfig(mu=(400.0, 500.0), n1=1, n2=2, m=2, d=7, seed=3)
    N = 2 * layering.HOST_SLAB_ELEMS // 16 + 2
    jobs = make_jobs(cfg, 2, K=40, M=4, N=N)
    rng = np.random.default_rng(cfg.seed)
    rng.exponential(1.0 / cfg.arrival_rate, size=2)
    lim = 1 << (cfg.m * cfg.d - 2)
    for job in jobs:
        assert job.a.dtype == job.b.dtype == np.int16
        for got, shape in ((job.a, (40, 4)), (job.b, (40, N))):
            np.testing.assert_array_equal(
                got, rng.integers(-lim, lim, size=shape, dtype=np.int64))


def _metrics_result(released, L=3):
    """Minimal RuntimeResult with just the fields the metrics under test
    read (released + layer_compute's L)."""
    J = len(released)
    return RuntimeResult(
        arrivals=np.zeros(J), starts=np.zeros(J), ends=np.zeros(J),
        layer_compute=np.zeros((J, L)), success=np.ones((J, L), bool),
        terminated=np.zeros(J, bool), kappa=np.zeros(3, dtype=np.int64),
        released=np.asarray(released, dtype=np.int64))


class TestMetrics:
    def test_format_delay_table_empty_rows(self):
        """Regression: an empty table (e.g. a run terminated before any
        release) must render a placeholder, not IndexError on rows[0]."""
        assert format_delay_table([]) == "(no resolutions to report)"

    def test_format_delay_table_none_percentiles(self):
        table = format_delay_table([{
            "resolution": 0, "mean_delay": float("inf"),
            "p50_delay": None, "p95_delay": None, "success_rate": 0.0}])
        assert "-" in table and "res" in table

    def test_release_histogram_counts_and_dtype(self):
        res = _metrics_result([-1, 0, 0, 2, 1, 2, 2], L=3)
        hist = res.release_histogram()
        assert hist.tolist() == [1, 2, 1, 3]     # none, res0, res1, res2
        assert hist.sum() == res.num_jobs

    def test_release_histogram_empty_and_single_bin(self):
        assert _metrics_result([], L=3).release_histogram().tolist() == \
            [0, 0, 0, 0]
        # all jobs unreleased: histogram still spans every resolution
        assert _metrics_result([-1, -1], L=2).release_histogram().tolist() \
            == [2, 0, 0]


class TestEndToEnd:
    def test_completes_and_decode_verifies(self):
        """No stragglers, no deadline: every job reaches full resolution
        and every resolution bit-matches the exact layered oracle (to
        float64 decode precision)."""
        cfg = RuntimeConfig(mu=(400.0, 650.0, 380.0), arrival_rate=100.0,
                            complexity=0.2, straggler="none", seed=0)
        res, futures = run_jobs(cfg, num_jobs=6, K=64, M=8, N=8, verify=True)
        assert res.success.all()
        assert (res.released == cfg.num_layers - 1).all()
        assert not res.terminated.any()
        assert np.nanmax(res.verify_errors) < 1e-9
        # the futures hold the actual products
        jobs = make_jobs(cfg, 6, K=64, M=8, N=8)
        exact = jobs[0].a.T.astype(np.int64) @ jobs[0].b
        np.testing.assert_allclose(futures[0].resolution(cfg.num_layers - 1),
                                   exact, rtol=1e-9)

    def test_deadline_releases_verified_lower_resolution(self):
        """The acceptance scenario: an injected straggler plus a deadline
        the final resolution misses — the run still releases a correct
        (decode-verified) lower resolution, and measured per-resolution
        mean delays are ordered res0 < ... < final.

        The deadline is calibrated against a measured deadline-free
        baseline of the same stall regime (not a hard-coded wall-clock
        constant): resolution 0 must always make it (the assertion
        below), which only holds if the deadline comfortably clears this
        machine's actual res-0 service time — 30 ms is plenty on an idle
        box but flaky under CI load.  2.2x the measured res-0 mean keeps
        the final resolution impossible (the stalled worker holds it back
        by stall_seconds = 2 s) while making res 0 safe by construction.
        """
        base = dict(mu=(400.0, 650.0, 380.0), arrival_rate=14.0,
                    complexity=8.0, straggler="stall", stall_workers=(2,),
                    stall_seconds=2.0, seed=0)
        probe, _ = run_jobs(RuntimeConfig(**base), num_jobs=6,
                            K=64, M=8, N=8)
        deadline = max(0.030, 2.2 * float(probe.layer_compute[:, 0].mean()))
        cfg = RuntimeConfig(deadline=deadline, **base)
        res, futures = run_jobs(cfg, num_jobs=20, K=64, M=8, N=8,
                                verify=True)
        assert res.terminated.any()              # the deadline binds
        sr = res.success_rate()
        assert sr[0] == pytest.approx(1.0)       # §IV regime: res 0 always
        assert sr[-1] < 1.0                      # final resolution missed
        term = np.flatnonzero(res.terminated)
        assert (res.released[term] >= 0).all()   # partials still shipped
        assert (res.released[term] < cfg.num_layers - 1).any()
        # every released resolution is decode-verified vs the exact oracle
        assert np.nanmax(res.verify_errors) < 1e-9
        # MSB-first delay ordering, qualitatively matching simulate()
        md = res.mean_delay()
        assert np.all(np.diff(md) > 0)
        sim = simulator.simulate(cfg.to_system_config(), 2000, layered=True,
                                 seed=0)
        assert np.all(np.diff(sim.mean_delay()) > 0)

    def test_termination_requires_queued_successor(self):
        """A single job can blow way past the deadline: with nothing
        queued behind it, §IV never terminates it."""
        cfg = RuntimeConfig(mu=(400.0, 650.0, 380.0), arrival_rate=100.0,
                            complexity=4.0, deadline=1e-4,
                            straggler="exp", seed=1)
        res, _ = run_jobs(cfg, num_jobs=1, K=64, M=8, N=8)
        assert not res.terminated[0]
        assert res.success[0].all()
        assert res.layer_compute[0, -1] > 1e-4   # deadline WAS exceeded

    def test_purged_tasks_are_reclaimed(self):
        """Stale coded tasks are purged at fusion: with T - k = 2 spare
        tasks per round, late results are dropped, and the stalled
        worker's queue never blocks later rounds."""
        cfg = RuntimeConfig(mu=(400.0, 650.0, 380.0), arrival_rate=50.0,
                            complexity=1.0, straggler="stall",
                            stall_workers=(2,), stall_seconds=2.0, seed=0)
        res, _ = run_jobs(cfg, num_jobs=4, K=64, M=8, N=8)
        assert res.success.all()                 # stall never blocks fusion
        # worker 2 (kappa=1) never completed a task: all purged or pending
        assert res.stale_results >= 0
        assert res.wall_elapsed < 1.5            # not serialized behind stalls

    def test_runtime_agrees_with_simulator(self):
        """Measured mean first-resolution delay under the exp straggler
        model agrees with simulate() on the same configuration.

        Delay scales (~25 ms/task) are chosen to dominate the container's
        timer granularity (Event.wait oversleeps ~1-3 ms per wait) and the
        ~1 ms/round master overhead; at this scale the measured/simulated
        ratio sits around 1.1."""
        cfg = RuntimeConfig(mu=(400.0, 650.0, 380.0), arrival_rate=2.0,
                            complexity=40.0, straggler="exp", seed=2)
        res, _ = run_jobs(cfg, num_jobs=12, K=64, M=8, N=8)
        sim = simulator.simulate(cfg.to_system_config(), 4000, layered=True,
                                 seed=7)
        md, sd = res.mean_delay(), sim.mean_delay()
        assert md[0] == pytest.approx(sd[0], rel=0.30)
        # ordering agrees across ALL resolutions
        assert np.all(np.diff(md) > 0) and np.all(np.diff(sd) > 0)

    def test_stage_timings_recorded(self):
        """Every pipeline stage is accounted and the per-round master
        overhead (encode + decode) is well under a millisecond."""
        from repro.runtime.metrics import STAGES

        cfg = RuntimeConfig(mu=(400.0, 650.0, 380.0), arrival_rate=100.0,
                            complexity=0.2, straggler="none", seed=0)
        res, _ = run_jobs(cfg, num_jobs=8, K=64, M=8, N=8)
        assert set(res.stage_seconds) == set(STAGES)
        assert res.stage_rounds == 8 * cfg.num_rounds
        assert all(v >= 0.0 for v in res.stage_seconds.values())
        assert res.stage_seconds["encode"] > 0.0
        assert res.stage_seconds["decode"] > 0.0
        assert np.isfinite(res.per_round_overhead())
        # generous ceiling (loaded CI runners): the dev-container value is
        # ~300 us/round; the hard perf gate lives in the bench regression
        # check, not here
        assert res.per_round_overhead() < 1e-2

    def test_zero_copy_round_batches(self):
        """dispatch_round hands each worker a view into the round's coded
        buffers — no per-task copies."""
        from repro.runtime.tasks import RoundBatch
        from repro.runtime.worker import WorkerPool

        cfg = RuntimeConfig(mu=(400.0, 650.0, 380.0), straggler="none")
        seen = []
        pool = WorkerPool(cfg, sink=lambda r: None)
        for w in pool.workers:       # don't start threads; inspect queues
            w.submit_round = seen.append
        code = cfg.code()
        X = np.zeros((cfg.total_tasks, 8, 4))
        Y = np.zeros((cfg.total_tasks, 8, 4))
        pool.dispatch_round(RoundContext(0, 0), X, Y, cfg.load_split())
        assert sum(b.count for b in seen) == cfg.total_tasks
        for batch in seen:
            assert isinstance(batch, RoundBatch)
            assert batch.x.base is X and batch.y.base is Y   # views
            np.testing.assert_array_equal(
                batch.x, X[batch.first_task_id:
                           batch.first_task_id + batch.count])

    def test_trace_driven_arrivals(self):
        """Explicit arrival traces (batch-at-once) are honoured: jobs
        queue FIFO and starts are spaced by service, not arrivals."""
        cfg = RuntimeConfig(mu=(400.0, 650.0, 380.0), complexity=0.2,
                            straggler="none", seed=0)
        res, _ = run_jobs(cfg, num_jobs=4, K=64, M=8, N=8,
                          arrivals=[0.0, 0.0, 0.0, 0.0])
        assert res.success.all()
        assert np.all(np.diff(res.starts) >= -1e-9)
        # FIFO: each job starts where the previous one ended
        np.testing.assert_allclose(res.starts[1:], res.ends[:-1], atol=5e-3)
