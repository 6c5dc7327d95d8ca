"""CPU tests of the benchmark's own arithmetic: trace reduction, roofline,
the seeded sample, the reference's preconditioners, and lookup of its
parts by name."""
import gzip
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402
import loadgen  # noqa: E402
import metriclib  # noqa: E402
import roofline  # noqa: E402
import spec as spec_mod  # noqa: E402

FIXTURES = Path(__file__).resolve().parent / "fixtures"


# -- trace reduction ---------------------------------------------------------

def _mini_trace():
    """Two devices, hand-placed ops (ns): back to back, nested and apart."""
    return {
        "window_ns": 1000.0,
        "devices": {
            "/device:TPU:0": [
                ["nekbone_ax_slab_n10_sz2", 100, 200, True],   # 100-300
                ["fusion.1", 300, 50, False],                  # 300-350
                ["nekbone_cg_update_n10_sz2", 500, 100, True],  # 500-600
                ["all-reduce.3", 700, 50, False],              # 700-750
            ],
            "/device:TPU:1": [
                ["nekbone_ax_slab_n10_sz2", 0, 400, True],     # 0-400
                ["copy.2", 100, 50, False],                    # nested
            ],
        },
        "host": [["PjitFunction(solve)", 0, 1000],
                 ["wait_for_request", 340, 170]],
    }


def test_busy_is_the_union_of_op_intervals():
    s = devtrace.summarize(_mini_trace())
    d0, d1 = s["devices"]["/device:TPU:0"], s["devices"]["/device:TPU:1"]
    assert d0["busy_ns"] == 250 + 100 + 50      # [100,350] [500,600] [700,750]
    assert d1["busy_ns"] == 400
    assert s["mean"]["busy_ns"] == (400 + 400) / 2


def test_pallas_collective_other_split():
    s = devtrace.summarize(_mini_trace())
    d0 = s["devices"]["/device:TPU:0"]
    assert (d0["pallas_ns"], d0["collective_ns"], d0["other_ns"]) == \
        (300, 50, 50)
    assert s["mean"]["pallas_ns"] == (300 + 350) / 2


def test_idle_gaps_are_named_by_the_innermost_host_span():
    s = devtrace.summarize(_mini_trace())
    (name, length), *_ = s["idle_gaps"]
    assert (name, length) == ("wait_for_request", 150)     # 350-500


def test_idle_share_and_per_iteration_readers():
    run = _run(trace=devtrace.summarize(_mini_trace()),
               solves=[{"iters": 2}, {"iters": 3}])
    assert metriclib.idle_share(run) == pytest.approx(1 - 400 / 1000)
    assert metriclib.per_iter_ms(run, "pallas_ns") == pytest.approx(
        325 / 1e6 / 5)
    assert metriclib.per_iter_ms(_run(trace=None), "pallas_ns") is None


def test_chip_recorded_trace_fixture_reduces():
    """A trace recorded on a TPU v5 lite chip (one cg100 solve through the
    v1 pipeline, in the loader's JSON form): the ``while`` op that spans
    the loop body counts no time of its own, so the ops' own times add up
    to the busy time, and the one Pallas kernel is told apart from XLA's
    ops."""
    with gzip.open(FIXTURES / "trace_cg100.json.gz", "rt") as f:
        trace = json.load(f)
    s = devtrace.summarize(trace)
    dev = s["devices"]["/device:TPU:0"]
    assert s["n_devices"] == 1
    assert 0 < dev["pallas_ns"] < dev["busy_ns"] <= trace["window_ns"]
    assert dev["pallas_ns"] + dev["other_ns"] + dev["collective_ns"] == \
        pytest.approx(dev["busy_ns"], rel=1e-6)
    ops = dict(s["top_ops"])
    assert "nekbone_ax_pap_n10_be128_accfloat32" in ops
    assert ops["nekbone_ax_pap_n10_be128_accfloat32"] == dev["pallas_ns"]
    assert "while" not in ops


def test_nested_ops_count_their_own_time_only():
    evs = [["while.1", 0, 100, False], ["nekbone_a.2", 10, 30, True],
           ["fusion.3", 50, 20, False], ["copy.4", 55, 5, False]]
    assert devtrace.self_times(evs) == [50.0, 30.0, 15.0, 5.0]
    assert devtrace.op_name("%nekbone_ax_slab_n8_sz4.6 = (f32[8]) "
                            "custom-call(%x), custom_call_target=\"t\"") == \
        "nekbone_ax_slab_n8_sz4.6"
    assert devtrace.base_name("copy.44") == "copy"


# -- roofline ----------------------------------------------------------------

V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_eq1_count():
    assert roofline.flops_per_dof_iter(10) == 154
    assert roofline.flops_per_dof_iter(8) == 130


def test_roofline_cell_nekbone_p9_e4096():
    # 4096 elements of 10^3 nodes, one 100-iteration solve:
    # 4,096,000 x 100 x 154 = 6.30784e10 operations -> 320.2 us at 197 TF/s;
    # 2 x 4,096,000 x 4 B = 32.768 MB -> 40.0 us at 819 GB/s: compute bound.
    t, bound = roofline.t_roof_s(dofs=4096 * 1000, n=10, iterations=100,
                                 solves=1, peak=V5E)
    assert bound == "compute"
    assert t == pytest.approx(6.30784e10 / 197e12)
    assert t == pytest.approx(320.195e-6, rel=1e-5)


def test_roofline_cell_ceed_bp5_p7_e8192():
    # 8192 elements of 8^3 nodes, 500 iterations: 4,194,304 x 500 x 130
    # = 2.7263e11 operations -> 1.384 ms; bytes 33.55 MB -> 41 us.
    t, bound = roofline.t_roof_s(dofs=8192 * 512, n=8, iterations=500,
                                 solves=1, peak=V5E)
    assert bound == "compute"
    assert t == pytest.approx(4194304 * 500 * 130 / 197e12)
    # one iteration of one solve is memory bound
    t1, bound1 = roofline.t_roof_s(dofs=8192 * 512, n=8, iterations=1,
                                   solves=1, peak=V5E)
    assert bound1 == "memory"
    assert t1 == pytest.approx(2 * 4194304 * 4 / 819e9)


def test_roofline_share_reader_averages_chip_shares():
    trace = {"n_devices": 2, "window_ns": 1e9,
             "devices": {"a": {"busy_ns": 1e6}, "b": {"busy_ns": 2e6}},
             "mean": {"busy_ns": 1.5e6}}
    run = _run(trace=trace, solves=[{"iters": 100}], dofs=2 * 4096 * 1000)
    t, _ = roofline.t_roof_s(dofs=4096 * 1000, n=10, iterations=100,
                             solves=1, peak=V5E)
    assert metriclib.roofline_pct(run) == pytest.approx(
        100 * (t / 1e-3 + t / 2e-3) / 2)


def test_peaks_table_refuses_unknown_devices():
    assert roofline.peaks("TPU v5 lite") == V5E
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


# -- load generation ---------------------------------------------------------

def test_reservoir_sample_depends_on_the_seed_only():
    def draw(seed):
        r = loadgen.Reservoir(4, seed)
        for i in range(100):
            r.offer(i)
        return sorted(r.items)
    assert draw(7) == draw(7)
    assert draw(7) != draw(8)
    assert len(set(draw(7))) == 4


def test_the_closed_loop_waits_for_each_answer_and_samples_all():
    """One caller, back to back: solve ``i`` takes right-hand side
    ``i mod pool``, starts when the one before has ended, and every
    answer reaches the sample with its iterations."""
    closed = spec_mod.Spec().loop("closed")
    now = [0.0]

    def clock():
        return now[0]

    class Fake:
        def solve(self, k):
            now[0] += 0.25
            return f"x{k}", 10 + k

    sample = loadgen.Reservoir(100, 5)
    out = closed.drive(Fake(), [0, 1, 2], {}, 1.0, 5, sample, clock)
    assert [s["rhs"] for s in out["solves"]] == [0, 1, 2, 0]
    assert [s["iters"] for s in out["solves"]] == [10, 11, 12, 10]
    assert all(b["start"] == a["end"] for a, b in
               zip(out["solves"], out["solves"][1:]))
    assert out["window_s"] == 1.0
    assert out["report"]["solve_s_p50"] == 0.25
    assert sample.items == [(0, "x0", 10), (1, "x1", 11), (2, "x2", 12),
                            (0, "x0", 10)]


# -- lookup by name ----------------------------------------------------------

def test_the_benchmark_finds_its_parts_by_name():
    sp = spec_mod.Spec()
    for w in sp.data["workloads"]:
        cfg = sp.config(w["config"])
        assert cfg["name"] == w["config"]
        traffic = sp.traffic(w["traffic"])
        assert callable(sp.loop(traffic["loop"]).drive)
        solver = {**cfg["solver"], **traffic.get("solver", {})}
        assert callable(sp.system(solver["entry"]).build)
        assert hasattr(sp.reference(cfg["reference"]), "Box")
        for trace in (False, True):
            for m in sp.metrics_for(w["name"], trace):
                assert callable(sp.metric(m["name"]).read)
    for m in sp.data["end_to_end"] + sp.data["per_layer"]:
        assert callable(sp.metric(m["name"]).read)


def test_a_file_added_in_another_directory_is_found(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(BENCH / "metrics", bench / "metrics")
    shutil.copytree(BENCH / "traffic", bench / "traffic")
    (bench / "traffic" / "burst.json").write_text(
        json.dumps({"loop": "onoff", "rate_per_s": 40.0, "pool": 2,
                    "sample": 2, "solver": {"entry": "sharded"}}))
    (bench / "loops").mkdir()
    (bench / "loops" / "onoff.py").write_text(
        "def drive(system, pool, traffic, seconds, seed, sample, clock):\n"
        "    return {'solves': [], 'window_s': seconds}\n")
    (bench / "systems").mkdir()
    (bench / "systems" / "sharded.py").write_text(
        "def build(cfg, solver, traffic, pool, tols):\n"
        "    return ('sharded', len(pool))\n")
    (bench / "metrics" / "answers.count.py").write_text(
        "def read(run):\n    return float(len(run.loop['solves']))\n")
    data = json.loads((spec_mod.ROOT / "BENCHMARK.json").read_text())
    cfg = data["configs"][0]
    (tmp_path / "c.json").write_text(json.dumps({"name": cfg["name"]}))
    cfg["file"] = "c.json"
    data["workloads"] = [{"name": "x.burst", "config": cfg["name"],
                          "traffic": "burst", "chips": 1, "why": "test"}]
    data["per_layer"] = [{"name": "answers.count", "unit": "solves",
                          "better": "higher", "source": "program_counter",
                          "layer": "t", "moves": "setup_s"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
    sp = spec_mod.Spec(tmp_path, bench)
    assert sp.config(cfg["name"]) == {"name": cfg["name"]}
    mix = sp.traffic("burst")
    assert mix["rate_per_s"] == 40.0
    assert sp.loop(mix["loop"]).drive(None, [], mix, 2.0, 1, None, None) == \
        {"solves": [], "window_s": 2.0}
    assert sp.system(mix["solver"]["entry"]).build({}, {}, mix, [1, 2],
                                                   []) == ("sharded", 2)
    assert [m["name"] for m in sp.metrics_for("x.burst", True)] == \
        ["answers.count"]
    assert sp.metric("answers.count").read(_run(solves=[{}, {}])) == 2.0
    with pytest.raises(KeyError):
        sp.traffic("missing")
    with pytest.raises(KeyError):
        sp.system("case")
    with pytest.raises(KeyError):
        sp.cell("nope")


def test_the_reference_names_its_own_preconditioners():
    """The preconditioner's residual weight comes from the reference
    module; one it does not have is refused there."""
    ref = spec_mod.Spec().reference("sem_poisson")
    box = ref.Box(4, (2, 2, 2))
    assert np.array_equal(box.precond_weight(None), np.ones(box.gshape))
    assert np.allclose(box.precond_weight("jacobi") * box.diag64(), 1.0)
    with pytest.raises(ValueError):
        box.precond_weight("pmg")
    with pytest.raises(ValueError):
        box.solver(precond="cheb")


def _run(trace=None, solves=(), dofs=4096 * 1000):
    import types
    return types.SimpleNamespace(loop={"solves": list(solves)}, trace=trace,
                                 n=10, dofs=dofs, peak=V5E, window_s=1.0)
