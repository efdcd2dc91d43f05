"""The trace reduction on a small trace recorded on a TPU v5e.

``data/scan24.xplane.pb``: five calls, each under a ``bench:dispatch`` host
span inside ``bench:window``, of one jitted program that scans 24 layers of
a (1024, 1024) matmul under ``obs:grad`` and an elementwise blend under
``obs:consensus``.  The reduction is checked against its own arithmetic and
against a second reader of the same file (``jax.profiler.ProfileData``).
"""

import importlib.util
import os

import numpy as np
import pytest

from bench.tests import smoke  # noqa: F401
from bench.metrics import trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "scan24.xplane.pb")


@pytest.fixture(scope="module")
def t():
    return tr.Trace(DATA)


def test_reads_the_device_and_the_host_spans(t):
    assert list(t.devices) == [0]
    w = t.window()
    assert w[1] > w[0]
    names = [s[0] for s in t.spans]
    assert names.count("bench:dispatch") == 5
    mods = t.modules(0, w)
    assert len(mods["name"]) == 5 and all(n.startswith("jit_f(") for n in mods["name"])


def test_busy_union_matches_a_second_reader(t):
    from jax.profiler import ProfileData

    w = t.window()
    pd = ProfileData.from_file(DATA)
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    line = next(ln for ln in plane.lines if ln.name == "XLA Ops")
    # the same shift of the device clock as the reduction makes
    first_span = min(s[1] for s in t.spans if s[0] != "bench:window")
    first_dev = min(e.start_ns for e in line.events)
    shift = max(0.0, first_span - first_dev)
    # a loop's ``while`` spans its body: the union counts only the body's ops
    ev = [(e.start_ns + shift, e.start_ns + shift + e.duration_ns)
          for e in line.events if w[0] <= e.start_ns + shift < w[1]
          and not e.name.startswith("%while ")]
    ev.sort()
    union, cur_s, cur_e = 0.0, None, None
    for s, e in ev:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                union += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    union += cur_e - cur_s
    busy = tr.busy_ns(t, 0, w)
    # the second reader rounds each event to whole nanoseconds
    assert busy == pytest.approx(union, rel=1e-4)
    assert 0 < busy <= w[1] - w[0]


def test_scopes_split_the_busy_time(t):
    w = t.window()
    grad = tr.scope_ns(t, 0, w, "obs:grad")
    cons = tr.scope_ns(t, 0, w, "obs:consensus")
    busy = tr.busy_ns(t, 0, w)
    # XLA fuses the blend into the matmul's fusion: the grad scope holds it
    assert grad > 0 and cons == 0.0
    assert grad <= busy * 1.0001
    # the matmul scope holds the convolution fusions
    o = t.ops(0, w)
    conv = sum(d for d, c, s in zip(o["dur"], o["category"], o["tf_op"])
               if "convolution" in c and "obs:grad" in s)
    assert conv > 0.5 * grad


class _Fake:
    """One device whose loop (``while``, 0-10) runs two body ops, 0-2 and
    5-7, and idles between them."""

    def __init__(self):
        self.devices = {0: None}
        self.spans = [("bench:dispatch", 0.0, 10.0)]

    def ops(self, dev, window):
        return dict(start=np.array([0.0, 0.0, 5.0]), end=np.array([10.0, 2.0, 7.0]),
                    dur=np.array([10.0, 2.0, 2.0]), name=["while", "a", "b"],
                    tf_op=["", "obs:grad/a", "obs:grad/b"],
                    category=["while", "loop fusion", "loop fusion"])


def test_idle_inside_a_loop_counts_as_idle():
    f = _Fake()
    assert tr.busy_ns(f, 0, (0.0, 10.0)) == 4.0
    assert tr.idle_gaps(f, (0.0, 10.0)) == [["bench:dispatch", 6.0 / 1e9]]
    assert tr.scope_ns(f, 0, (0.0, 10.0), "obs:grad") == 4.0


def test_idle_gaps_add_up_to_the_idle_time(t):
    w = t.window()
    idle = (w[1] - w[0]) - tr.busy_ns(t, 0, w)
    gaps = tr.idle_gaps(t, w)
    assert sum(v for _, v in gaps) * 1e9 == pytest.approx(idle, rel=1e-6, abs=10.0)
    assert {k for k, _ in gaps} <= {"bench:dispatch", "(no host span)"}


def test_top_ops_leave_out_loop_containers(t):
    w = t.window()
    top = tr.top_device_ops(t, w, n=50)
    assert not any(k.endswith("| while") for k, _ in top)
    assert sum(v for _, v in top) * 1e9 <= tr.busy_ns(t, 0, w) * 1.0001


def test_exposed_time_of_ops_that_overlap_nothing(t):
    w = t.window()
    o = t.ops(0, w)
    fus = sum(d for d, c in zip(o["dur"], o["category"]) if "convolution" in c)
    # ops on the one line of a chip run one after another: none is hidden
    assert tr.exposed_ns(t, 0, w, ("convolution",)) == pytest.approx(fus, rel=1e-6)
    assert tr.exposed_ns(t, 0, w, tr.COLLECTIVE_CATEGORIES) == 0.0


def test_busy_intervals():
    s, e = tr.busy_intervals(np.array([0.0, 1.0, 5.0, 6.0]),
                             np.array([2.0, 3.0, 5.5, 7.0]))
    np.testing.assert_allclose(s, [0.0, 5.0, 6.0])
    np.testing.assert_allclose(e, [3.0, 5.5, 7.0])


def _reader(name):
    path = os.path.join(os.path.dirname(tr.__file__), name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class _Ctx:
    def __init__(self, t, kind, counts, cfg=None, job=None, host=None):
        self.trace, self.kind, self.counts = t, kind, counts
        self.window = t.window()
        self.window_s = (self.window[1] - self.window[0]) / 1e9
        self.devices, self.chips = [0], 1
        self.cfg, self.job, self.host = cfg or smoke.QWEN, job or {"seq_len": 64}, host or {}
        self.peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_readers(t):
    ctx = _Ctx(t, "train", {"steps": 5, "tokens": 5 * 128})
    idle = _reader("device_idle_share.train")(ctx)
    w = ctx.window
    assert idle == pytest.approx(100 * (1 - tr.busy_ns(t, 0, w) / (w[1] - w[0])))
    assert _reader("grad_ms.train")(ctx) == pytest.approx(
        tr.scope_ns(t, 0, w, "obs:grad") / 1e6 / 5)
    assert 0 < _reader("mfu.train")(ctx) < 100
    # readers that find nothing to read return nothing, never 0
    serve = _Ctx(t, "serve", {"decode_steps": [(2, 30)], "admitted": 1})
    assert _reader("mfu.decode_step.tpot")(serve) is None     # no jit_step program
    assert _reader("prefill_ms.ttft")(serve) is None          # no prefill scope
    assert _reader("mfu.train")(serve) is None
    assert _reader("tpot_p95_ms.overload")(_Ctx(t, "serve", {}, host={"tpot_ms": [1.0, 3.0]})) \
        == pytest.approx(2.9)


@pytest.mark.parametrize("listed", [True, False], ids=["listed", "unlisted"])
def test_a_listed_metric_that_finds_nothing_stops_the_run(t, listed):
    from bench.harness import common

    metric = {"name": "prefill_ms.ttft", "unit": "ms"}
    if listed:
        metric["workloads"] = ["smoke"]
    s = smoke.spec(smoke.QWEN, smoke.SERVE_JOB, {})
    s.per_layer = [metric]
    ctx = _Ctx(t, "serve", {"admitted": 1})          # no prefill scope in the trace
    if listed:
        with pytest.raises(RuntimeError, match="prefill_ms.ttft"):
            common.read_per_layer(s, ctx)
    else:
        assert common.read_per_layer(s, ctx) == {}
