"""SWF trace layer: parser round-trips, malformed-line tolerance, the
normalizer's monotone-rebase invariant, replay field mapping (tenants +
failure records), and the golden 200-job replay signature."""

import json
import os

from hypothesis import given, settings, strategies as st

from repro.core import ClusterSimulator, jobstate, traces
from repro.core.traces import (SWFJob, SWFTrace, emit_swf, normalize_trace,
                               parse_swf, replay_swf, synthetic_swf)

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(REPO_ROOT, "benchmarks", "data", "mini_cluster.swf")
KTH_FIXTURE = os.path.join(REPO_ROOT, "benchmarks", "data",
                           "kth_sp2_standin.swf")

# field strategies (ints bounded well under 2**53 so the float hop in the
# int-column parser stays exact)
_int = st.integers(min_value=-1, max_value=1 << 40)
_time = st.floats(min_value=-1.0, max_value=1e9,
                  allow_nan=False, allow_infinity=False)
_swf_row = st.tuples(_int, _time, _time, _time, _int, _time, _time, _int,
                     _time, _time, st.integers(min_value=-1, max_value=5),
                     _int, _int, _int, _int, _int, _int, _time)


def _job(row) -> SWFJob:
    return SWFJob(*row)


# ------------------------------------------------------------ parser/emitter
@settings(max_examples=60, deadline=None)
@given(st.lists(_swf_row, max_size=30))
def test_parse_emit_parse_roundtrip(rows):
    """parse → emit → parse is the identity on the job records."""
    jobs = tuple(_job(r) for r in rows)
    trace = SWFTrace(jobs, header=("Version: 2.2", "Note: property run"))
    text = emit_swf(trace)
    back = parse_swf(text)
    assert back.jobs == jobs
    assert back.header == trace.header
    assert back.skipped == 0
    # and the emitted text is a fixed point: emit(parse(emit(x))) == emit(x)
    assert emit_swf(back) == text


def test_malformed_lines_tolerated_and_counted():
    good = SWFJob(job_id=1, submit=10.0, run=5.0, procs=2, req_procs=2,
                  status=1, user=3, group=1)
    text = "\n".join([
        "; Version: 2.2",
        "",                                   # blank
        emit_swf((good,)).strip(),
        "   ",                                # whitespace-only
        "1 2 3",                              # short line
        "; trailing comment",
        "x y z " * 6,                         # 18 columns, non-numeric
        "7 30 -1 4 1 4 -1 1 9 -1 1 0 0 0 0 0 -1 -1 999 extra",  # extra cols ok
    ])
    trace = parse_swf(text)
    assert trace.jobs[0] == good
    assert len(trace.jobs) == 2               # good line + extra-columns line
    assert trace.jobs[1].job_id == 7
    assert trace.skipped == 2                 # short + non-numeric
    assert trace.header == ("Version: 2.2", "trailing comment")


def test_parse_accepts_string_or_lines():
    text = emit_swf((SWFJob(job_id=4, submit=1.0, run=2.0, procs=1,
                            status=1),))
    assert parse_swf(text).jobs == parse_swf(text.splitlines()).jobs


# --------------------------------------------------------------- normalizer
@settings(max_examples=60, deadline=None)
@given(st.lists(st.floats(min_value=0.0, max_value=1e7,
                          allow_nan=False, allow_infinity=False),
                min_size=1, max_size=40),
       st.sampled_from([0.25, 0.5, 1.0, 2.0, 4.0]))
def test_rebase_is_monotone_from_zero(submits, load_scale):
    """After normalize: submit times sorted, first at 0, gaps divided by
    exactly the load-scale factor."""
    jobs = [SWFJob(job_id=i + 1, submit=s, run=1.0, procs=1, status=1)
            for i, s in enumerate(submits)]
    out = normalize_trace(jobs, load_scale=load_scale)
    assert len(out) == len(jobs)
    times = [j.submit for j in out]
    assert times[0] == 0.0
    assert all(b >= a for a, b in zip(times, times[1:]))
    want = sorted(submits)
    for got, raw in zip(times, want):
        assert abs(got - (raw - want[0]) / load_scale) < 1e-6


def test_normalize_clamps_and_truncates():
    jobs = [SWFJob(job_id=1, submit=100.0, run=1.0, procs=700, req_procs=700,
                   status=1),
            SWFJob(job_id=2, submit=50.0, run=1.0, procs=4, status=1),
            SWFJob(job_id=3, submit=-1.0, run=1.0, procs=4, status=1)]  # unknown
    out = normalize_trace(jobs, max_jobs=1, max_procs=512)
    assert len(out) == 1
    assert out[0].job_id == 2                # sorted by submit; unknown dropped
    out = normalize_trace(jobs, max_procs=512)
    clamped = [j for j in out if j.job_id == 1][0]
    assert clamped.procs == 512 and clamped.req_procs == 512


# ------------------------------------------------------------------- replay
def test_replay_maps_tenants_walltime_and_failure_records():
    sim = ClusterSimulator(n_nodes=8, weight=1, check_nodes=False,
                           scheduler_period=1e9)
    jobs = [
        # completes fine, tenant ids mapped onto the fairness axes
        SWFJob(job_id=1, submit=0.0, run=50.0, req_procs=2, req_time=100.0,
               status=1, user=3, group=1),
        # trace-recorded failure: runs its logged time, dies as user fault
        SWFJob(job_id=2, submit=5.0, run=30.0, req_procs=1, req_time=100.0,
               status=0, user=4, group=2),
        # cancelled before it ever ran: skipped, never submitted
        SWFJob(job_id=3, submit=6.0, run=0.0, req_procs=1, status=5),
        # overran its request: killed by walltime enforcement, like the log
        SWFJob(job_id=4, submit=7.0, run=500.0, req_procs=1, req_time=60.0,
               status=1, user=3, group=1),
        # asks for more than the cluster: clamped, not rejected
        SWFJob(job_id=5, submit=8.0, run=10.0, req_procs=64, req_time=50.0,
               status=1, user=5, group=0),
    ]
    stats = replay_swf(sim, jobs)
    assert stats.submitted == 4 and stats.skipped == 1
    assert stats.failed_records == 1
    recs = {r.idJob: r for r in sim.run()}
    assert len(recs) == 4
    by_user = {r.user: r for r in recs.values()}
    assert by_user["u3"].project == "g1" and by_user["u4"].project == "g2"
    assert all(r.state in (jobstate.TERMINATED, jobstate.ERROR)
               for r in recs.values())                    # 100% terminal
    assert by_user["u4"].state == jobstate.ERROR          # failure record
    assert by_user["u5"].state == jobstate.TERMINATED
    assert len(by_user["u5"].resources) == 8              # clamped to cluster
    walltimed = [r for r in recs.values()
                 if r.user == "u3" and r.state == jobstate.ERROR]
    assert len(walltimed) == 1                            # the overrun kill
    assert abs(walltimed[0].stop - walltimed[0].start - 60.0) < 1e-6


# ---------------------------------------------------------- bundled fixture
def test_fixture_is_regenerable_from_the_seeded_generator():
    """The bundled SWF fixture must equal synthetic_swf's seeded output —
    anyone can resize/regenerate it, and nobody can hand-edit it silently."""
    with open(FIXTURE) as fh:
        assert fh.read() == emit_swf(synthetic_swf(600, seed=7, max_procs=512))


def test_fixture_parses_clean():
    trace = traces.load_swf(FIXTURE)
    assert len(trace.jobs) == 600 and trace.skipped == 0
    assert any("MaxProcs: 512" in h for h in trace.header)
    out = normalize_trace(trace.jobs)
    assert out[0].submit == 0.0
    assert all(b.submit >= a.submit for a, b in zip(out, out[1:]))


# ------------------------------------------------------- golden replay trace
def test_swf_replay_matches_golden_signature():
    """First 200 jobs of the bundled trace on the 512-node simulator: the
    schedule signature (starts, stops, states, exact resource sets) must be
    byte-identical to the pinned baseline — the determinism anchor the CI
    trace-replay-smoke guard cross-checks against the same file."""
    from benchmarks.swf_replay import GOLDEN_JOBS, GOLDEN_LOAD, replay
    with open(os.path.join(GOLDEN_DIR, "swf_replay.json")) as fh:
        golden = json.load(fh)
    r = replay(max_jobs=GOLDEN_JOBS, load_scale=GOLDEN_LOAD)
    assert r.submitted == golden["submitted"]
    assert r.skipped == golden["skipped"]
    assert r.terminal == golden["terminal"] == r.submitted  # 100% terminal
    assert r.completed == golden["completed"]
    assert r.failed == golden["failed"]
    assert r.utilisation == golden["utilisation"]
    assert r.virtual_makespan_s == golden["virtual_makespan_s"]
    assert r.signature == golden["sha256"], \
        "SWF replay schedule diverged from the pinned golden baseline"


# ------------------------------------------------------- KTH-SP2 data drop
def test_kth_standin_is_regenerable_from_the_seeded_generator():
    """The bundled KTH-SP2 stand-in (100-processor SP2 shape, ~60% offered
    load at natural arrival rate) must equal the seeded generator output —
    same no-silent-hand-edits contract as the mini_cluster fixture. The
    real archive log is fetched by benchmarks/data/fetch_kth_sp2.py on
    hosts with network; the stand-in is what the golden signature pins."""
    with open(KTH_FIXTURE) as fh:
        assert fh.read() == emit_swf(synthetic_swf(
            900, seed=1996, max_procs=100, mean_interarrival=620.0,
            n_users=60, n_groups=10))


def test_kth_standin_parses_clean():
    trace = traces.load_swf(KTH_FIXTURE)
    assert len(trace.jobs) == 900 and trace.skipped == 0
    assert any("MaxProcs: 100" in h for h in trace.header)
    assert all(j.req_procs <= 100 for j in trace.jobs)


def test_kth_replay_matches_golden_signature():
    """First 150 jobs of the stand-in on the 100-node simulator — the
    second determinism anchor, pinned in tests/golden/kth_sp2.json and
    cross-checked by the CI trace-replay-smoke guard."""
    from benchmarks.swf_replay import (KTH_GOLDEN_JOBS, KTH_GOLDEN_LOAD,
                                       KTH_NODES, KTH_TRACE, replay)
    with open(os.path.join(GOLDEN_DIR, "kth_sp2.json")) as fh:
        golden = json.load(fh)
    r = replay(max_jobs=KTH_GOLDEN_JOBS, load_scale=KTH_GOLDEN_LOAD,
               nodes=KTH_NODES, trace_path=KTH_TRACE)
    assert r.submitted == golden["submitted"]
    assert r.skipped == golden["skipped"]
    assert r.terminal == golden["terminal"] == r.submitted  # 100% terminal
    assert r.completed == golden["completed"]
    assert r.failed == golden["failed"]
    assert r.utilisation == golden["utilisation"]
    assert r.virtual_makespan_s == golden["virtual_makespan_s"]
    assert r.signature == golden["sha256"], \
        "KTH-SP2 stand-in replay diverged from the pinned golden baseline"
