"""Hypothesis property tests over the WHOLE control plane: random workloads
through the simulator (real SQL, real meta-scheduler, real launcher), then
assert the system invariants that must hold for any workload:

  I1  capacity:       procs in use never exceed cluster capacity
  I2  exclusivity:    a resource never runs two jobs at once
  I3  liveness:       every non-best-effort job terminates (no famine)
  I4  causality:      start ≥ submission; stop − start = duration
  I5  conservation:   every terminated job got exactly nbNodes resources
"""

from hypothesis import given, settings, strategies as st

from repro.core import ClusterSimulator

job_st = st.tuples(
    st.floats(0, 50, allow_nan=False),       # submit time
    st.floats(1, 40, allow_nan=False),       # duration
    st.integers(1, 4),                       # nb_nodes
)
workload_st = st.lists(job_st, min_size=1, max_size=12)


def run_workload(jobs, **kw):
    sim = ClusterSimulator(n_nodes=4, weight=1, **kw)
    for at, dur, n in jobs:
        sim.submit(at, duration=dur, nb_nodes=n)
    recs = sim.run()
    return sim, recs


@settings(max_examples=25, deadline=None)
@given(workload_st)
def test_invariants_random_workload(jobs):
    sim, recs = run_workload(jobs)
    # I3 liveness + I4 causality
    for r in recs:
        assert r.state == "Terminated", r
        assert r.start is not None and r.start >= r.submit - 1e-9
        assert abs((r.stop - r.start) - r.duration) < 1e-6
    # I1 + I2: replay intervals per resource (assignments are captured by
    # the simulator while jobs run; the DB clears them on termination)
    per_res = {}
    for r in recs:
        for rid in r.resources:
            per_res.setdefault(rid, []).append((r.start, r.stop))
    for rid, ivs in per_res.items():
        ivs.sort()
        for (s1, e1), (s2, e2) in zip(ivs, ivs[1:]):
            assert s2 >= e1 - 1e-9, (rid, ivs)     # I2
    # I5 conservation — jobs enter the DB in event-time order, not list order
    by_submit = sorted(range(len(jobs)), key=lambda i: (jobs[i][0], i))
    for r in recs:
        want = jobs[by_submit[r.idJob - 1]][2]
        assert len(r.resources) == want, (r, want)


@settings(max_examples=15, deadline=None)
@given(workload_st, st.sampled_from(["fifo_backfill", "fifo",
                                     "sjf_resources", "easy_backfill",
                                     "greedy_small_first"]))
def test_liveness_any_policy(jobs, policy):
    """No policy may starve a regular job forever (the paper's no-famine
    default, §3.2.1)."""
    _, recs = run_workload(jobs, policy=policy)
    assert all(r.state == "Terminated" for r in recs)


@settings(max_examples=15, deadline=None)
@given(workload_st)
def test_makespan_lower_bound(jobs):
    """Makespan ≥ total work / capacity and ≥ the longest single job —
    the ESP efficiency denominator is a true lower bound."""
    sim, recs = run_workload(jobs)
    cap = 4
    work = sum(r.duration * r.procs for r in recs)
    makespan = max(r.stop for r in recs) - min(r.submit for r in recs)
    assert makespan + 1e-6 >= work / cap
    assert makespan + 1e-6 >= max(r.duration for r in recs)


@settings(max_examples=10, deadline=None)
@given(st.lists(st.tuples(st.floats(0, 30, allow_nan=False),
                          st.floats(1, 20, allow_nan=False)),
                min_size=1, max_size=8))
def test_besteffort_never_blocks_regular(jobs):
    """Best-effort jobs must never delay a regular job beyond what an empty
    cluster of running best-effort work can explain — regulars preempt."""
    sim = ClusterSimulator(n_nodes=2, weight=1)
    # saturate with long best-effort work
    for i in range(4):
        sim.submit(0.0, duration=500.0, nb_nodes=1, queue="besteffort",
                   max_time=1000.0)
    for at, dur in jobs:
        sim.submit(at + 1.0, duration=dur, nb_nodes=1)
    recs = sim.run()
    # by queue, not by id: resubmitted best-effort clones get ids above 4 too
    regular_ids = {row["idJob"] for row in sim.db.query(
        "SELECT idJob FROM jobs WHERE queueName != 'besteffort'")}
    regular = [r for r in recs if r.idJob in regular_ids]
    assert len(regular) == len(jobs)
    assert all(r.state == "Terminated" for r in regular)
    # a regular job's start is bounded by preemption latency, not by the
    # 500-second best-effort runtime
    for r in regular:
        assert r.start - r.submit < 400.0, r


@settings(max_examples=10, deadline=None)
@given(st.lists(st.tuples(st.floats(0, 40, allow_nan=False),
                          st.floats(1, 30, allow_nan=False),
                          st.integers(1, 3)),
                min_size=1, max_size=6),
       st.floats(60, 120, allow_nan=False))
def test_reservation_exactness_under_load(jobs, resv_start):
    """A granted reservation starts exactly at its slot regardless of the
    surrounding workload; if it cannot be granted it errors cleanly."""
    sim = ClusterSimulator(n_nodes=4, weight=1)
    for at, dur, n in jobs:
        sim.submit(at, duration=dur, nb_nodes=n, max_time=dur)
    sim.submit(0.5, duration=10, nb_nodes=2, reservation_start=resv_start)
    recs = sim.run()
    rid = sim.db.scalar("SELECT idJob FROM jobs WHERE reservation != 'None'")
    resv = next(r for r in recs if r.idJob == rid)
    assert resv.state in ("Terminated", "Error")
    if resv.state == "Terminated":
        assert abs(resv.start - resv_start) < 1e-6
        # no other job may use its 2 nodes during the slot
        for r in recs:
            if r.idJob == rid or r.state != "Terminated":
                continue
            overlap = (r.start < resv.stop - 1e-9 and
                       r.stop > resv.start + 1e-9)
            if overlap:
                assert len(r.resources & resv.resources) == 0
