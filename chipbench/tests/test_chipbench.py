"""Tests of the benchmark itself. Run by hand, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q

(tier-1 collects `tests/` only, and a benchmark PR adds no file there).
The `v5e:2x2` compile lives in one fixture in this one file, as the
on-chip-measurement guide sets out.
"""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import bytes_model, compare, spec, tracered  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture()
def clean_env():
    before = dict(os.environ)
    yield
    os.environ.clear()
    os.environ.update(before)


def _run_cell(cell: str, trace: int, seed: int = 12345) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", cell, "--seed", str(seed),
         "--seconds", "2", "--trace", str(trace), "--tiny"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


# -- every cell end to end at --tiny on the CPU ------------------------------


@pytest.mark.parametrize("cell", CELLS)
def test_cell_tiny_end_to_end(cell):
    line = _run_cell(cell, trace=0)
    assert LINE_KEYS <= set(line)
    assert line["correct"] is True, line["numbers"]
    assert line["failed"] == 0 and line["attempted"] > 0
    want = {m["name"] for m in spec.Cell(cell).end_to_end}
    assert set(line["metrics"]) == want
    assert all(m["value"] is None for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    # off the chip nothing is printed under the name of a device metric
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert list(line)[-1] == "numbers"
    # the fleet's persisted state is written once: a warm window fits
    # nothing, so the product's write-through log stays empty
    import glob

    logs = glob.glob(os.path.join(ROOT, "chipbench_state", "*", "*.log"))
    assert logs and all(os.path.getsize(p) == 0 for p in logs)


def test_cell_tiny_traced_prints_no_device_metric():
    cell = spec.Cell(CELLS[0])
    line = _run_cell(cell.name, trace=1, seed=2**31 + 77)
    counted = {m["name"] for m in cell.per_layer if m["source"] == "program_counter"}
    assert set(line["metrics"]) <= counted
    assert "compiles_in_window.sweep" in line["metrics"]
    assert line["metrics"]["compiles_in_window.sweep"]["value"] == 0
    assert line["metrics"]["arena_hit_pct.sweep"]["value"] == 100.0
    assert "busy_s" not in line["device"]


def test_no_result_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    `paths` the command exits non-zero and prints no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    shutil.copytree(
        os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0", "--tiny"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""),
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


def test_fails_without_a_chip():
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


# -- the faults a sweep cell can have come out as not correct ---------------


def _run_broken(monkeypatch, capsys, cell, plant) -> dict:
    """Drive a whole run (no look for a chip: --tiny) with the timed path
    broken from the moment the window opens."""
    from chipbench import run as runmod

    real_open = runmod.Context.window_open
    stack = contextlib.ExitStack()

    def window_open(self):
        real_open(self)
        stack.enter_context(plant())

    monkeypatch.setattr(runmod.Context, "window_open", window_open)
    with stack:
        runmod.main(["--workload", cell, "--seed", "99", "--seconds", "2", "--trace", "0", "--tiny"])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _cell_faults():
    """Every fault of every fleet kind, in the cell whose fleet has the most
    kinds beside it (each kind's faults are planted once)."""
    from chipbench import faults

    seen, out = set(), []
    for cell in sorted(CELLS, key=lambda c: len(spec.Cell(c).config["fleet"])):
        for name in faults.fleet_faults(spec.Cell(cell).config["fleet"]):
            if name not in seen:
                seen.add(name)
                out.append((cell, name))
    return out


@pytest.mark.parametrize("cell,fault", _cell_faults())
def test_fault_comes_out_not_correct(monkeypatch, capsys, clean_env, cell, fault):
    from chipbench import faults

    plant, number = faults.fleet_faults(spec.Cell(cell).config["fleet"])[fault]
    line = _run_broken(monkeypatch, capsys, cell, plant)
    assert line["correct"] is False
    value, limit = line["numbers"][number]
    assert value > limit


def test_a_fleet_kind_finds_its_faults_by_name():
    from chipbench import faults

    both = {"half_of_the_batch_left_out": "unjudged", "an_answer_altered": "flip_rate"}
    for kind in ("lstm", "univariate", "baseline", "bivariate"):
        assert {k: v[1] for k, v in faults.faults_of(kind).items()} == both
    with pytest.raises(SystemExit, match="faults/nokind.py"):
        faults.faults_of("nokind")


@pytest.mark.parametrize("cell,seed,room", [
    ("hybrid4-daily.warm-sweep", 5, 2.0),
    ("mixed-auto-daily.graded-sweep", 3, 2.0),
])
def test_control_in_bfloat16_comes_out_not_correct(cell, seed, room):
    """Every kind's reference in bfloat16, put in the program's place at a
    size a test can hold (the tiny fleet, every service compared, a window's
    15 sweeps): one of the cell's numbers has to fail."""
    from chipbench import control

    cell = spec.Cell(cell)
    out = control.control_margin(cell.sized(True), cell.traffic, seed=seed, sweeps=15)
    assert out["correct"] is False, out
    assert out["flip_rate"] > room * out["limit"]


@pytest.mark.parametrize("kind", ["univariate", "baseline", "bivariate"])
def test_a_kind_s_control_fails_the_kind_s_own_limit(kind):
    """Each new kind's control on its own rows, under the cell's own mix
    (its excursions straddle the kind's thresholds; half of the docs carry
    one here, so that 256 services stand for a run's spiked docs): the
    bfloat16 twin moves flags the float32 reference holds by more than the
    floor, and reads over the limit the configuration gives the kind; the
    float32 reference never disagrees with itself."""
    from chipbench import control

    cell = spec.Cell("mixed-auto-daily.graded-sweep")
    cfg = cell.sized(True)
    cfg["fleet"] = [dict(g, services=256) for g in cfg["fleet"] if g["kind"] == kind]
    traffic = dict(cell.traffic, spike_doc_share=0.5)
    job = control.ControlJob(cfg, traffic, seed=7, sweeps=8)
    g = cfg["fleet"][0]
    ref = compare.reference_of(kind)

    def history(uid):
        from chipbench import series

        return series.history(job.history_seed, uid, len(g["aliases"]), job.n_hist, job.fam)

    a = ref.judge(job.rows, g, cfg, history)
    b = ref.judge(job.rows, g, cfg, history)
    c = ref.judge(job.rows, g, cfg, history, control=True)
    assert (a["flags"] == b["flags"]).all() and a["flags"].any()
    assert a["flags"].shape == a["margins"].shape == (len(job.rows), job.w)
    # bfloat16 moves the scores: a point the reference holds by a margin
    # under the rounding it brings flips, one it holds widely may not
    moved = c["flags"] != a["flags"]
    assert moved.any()
    assert not (moved & (a["margins"] > 2.0)).any()
    numbers, _detail = compare.judge_sweeps(job, cfg, lambda m: None, control=True)
    value, limit = numbers["flip_rate." + kind]["value"], numbers["flip_rate." + kind]["limit"]
    assert limit is not None and value > limit, (value, limit)
    assert compare.verdict(numbers) is False


def test_a_kind_s_own_limit_decides():
    """A kind whose rows are a small share of the pool cannot hide under
    the pooled limit: its own number fails the run."""
    numbers = {
        "flip_rate": {"value": 1.0, "limit": 3.0},
        "flip_rate.lstm": {"value": 0.0, "limit": None},
        "flip_rate.bivariate": {"value": 9.0, "limit": 8.0},
    }
    assert compare.verdict(numbers) is False
    numbers["flip_rate.bivariate"]["value"] = 7.0
    assert compare.verdict(numbers) is True


def test_a_fleet_kind_finds_its_reference_by_name(monkeypatch):
    """A later PR's fleet kind brings chipbench/references/<kind>.py; the
    comparison and the control loop over the groups and edit nothing."""
    import types

    from chipbench import control

    calls = []

    def judge(rows, group, cfg, history, control=False, log=None):
        calls.append((group["kind"], len(rows), control))
        assert history(rows[0]["uid"]).shape == (len(group["aliases"]), cfg["history_points"])
        k, w = len(rows), rows[0]["sent"].shape[-1]
        flags = np.zeros((k, w), bool)
        flags[:, 0] = control
        return {"flags": flags, "margins": np.full((k, w), 0.5, np.float32)}

    monkeypatch.setitem(sys.modules, "chipbench.references.pair", types.SimpleNamespace(judge=judge))
    cfg = spec.Cell(CELLS[0]).sized(True)
    cfg["fleet"] = [
        {"kind": "pair", "aliases": ["a", "b"], "services": 24},
        {"kind": "pair", "aliases": ["a", "b", "c"], "services": 8},
    ]
    out = control.control_margin(cfg, {"sample_docs": 8, "spike_doc_share": 0.1, "spike_lo": 1, "spike_hi": 1}, seed=3, sweeps=2)
    assert {c[0] for c in calls} == {"pair"} and len(calls) == 4
    assert out["flip_margin"] == 0.5 and out["flip_rate"] == 1000.0 and out["correct"] is False
    with pytest.raises(SystemExit, match="references/nokind.py"):
        compare.reference_of("nokind")


# -- a fleet of several kinds -------------------------------------------------


def _config(name: str) -> dict:
    return json.load(open(os.path.join(ROOT, "chipbench", "configs", name + ".json")))


def test_fit_position_of_a_one_group_fleet_is_slot_mod_chunk():
    from chipbench import series

    cfg = _config("hybrid4-daily")
    group_of, _local = series.slot_layout(cfg["fleet"])
    chunk = int(cfg["env"]["FOREMAST_COLD_CHUNK_DOCS"])
    pos = series.fit_positions(cfg["fleet"], group_of, chunk)
    assert (pos == np.arange(len(group_of)) % chunk).all()


@pytest.mark.parametrize("light,slice_docs", [((16384, 8192, 8192), 16384), ((8192, 4096, 4096), 12288)])
def test_fit_position_by_kind_in_the_mixed_fleet(light, slice_docs):
    """Both fleets ISSUE 26 allows: every slice holds exactly 8,192 LSTM
    docs, and an LSTM doc's place in its fit batch is its rank among the
    LSTM docs of its cold chunk (the brute-force count agrees)."""
    from chipbench import series

    cfg = _config("mixed-auto-daily")
    groups = [dict(g) for g in cfg["fleet"]]
    for g, n in zip(groups[1:], light):
        g["services"] = n
    group_of, local = series.slot_layout(groups)
    assert len(group_of) == 32768 + sum(light)
    per_slice = [int((group_of[i:i + slice_docs] == 0).sum()) for i in range(0, len(group_of), slice_docs)]
    assert per_slice == [8192] * 4
    chunk = int(cfg["env"]["FOREMAST_COLD_CHUNK_DOCS"])
    assert slice_docs % chunk == 0
    pos = series.fit_positions(groups, group_of, chunk)
    for c0 in (0, 5 * chunk, len(group_of) - chunk):
        for gi in range(len(groups)):
            members = np.flatnonzero(group_of[c0:c0 + chunk] == gi) + c0
            assert (pos[members] == np.arange(len(members))).all()
    lstm = np.flatnonzero(group_of == 0)
    assert int((pos[lstm] != lstm % chunk).sum()) > 30000  # the old formula's misses
    # groups of one kind and alias count share a fit batch
    twins = [dict(groups[0], services=64), dict(groups[0], services=64, aliases=["a", "b", "c", "d"])]
    g2, _ = series.slot_layout(twins)
    assert (series.fit_positions(twins, g2, 32) == np.arange(128) % 32).all()


def test_a_canary_group_s_docs_carry_strategy_and_baseline(clean_env):
    from chipbench import fleet as fleetlib
    from chipbench import series

    cell = spec.Cell("mixed-auto-daily.graded-sweep")
    cfg = cell.sized(True)
    fl = fleetlib.Fleet(cfg, cell.traffic, seed=2**31 + 5)
    for slot in range(fl.slots):
        g = fl.groups[fl.group_of[slot]]
        doc = fl.store._docs[fl.doc_id[slot]]
        if g.get("baseline_window"):
            assert doc.strategy == "canary"
            assert doc.baseline_config.count("http://prom/base?") == len(g["aliases"])
        else:
            assert doc.strategy == "continuous" and doc.baseline_config == ""
    fl.draw_sample(8)
    fl.begin_sweep(3)
    canary = next(i for i, g in enumerate(fl.groups) if g.get("baseline_window"))
    slot = int(np.flatnonzero(fl.group_of == canary)[0])
    uid, alias = int(fl.uid[slot]), fl.groups[canary]["aliases"][0]
    t, v = fl.source.fetch(f"http://prom/base?q={alias}:app{uid}&step={fl.step}")
    assert len(t) == len(v) == fl.groups[canary]["baseline_window"]["points"]
    assert (np.diff(t) == fl.step).all() and t[0] == fl.cur_times[0] - 86_400
    assert (v == fl.base_values[canary][fl.local[slot], 0]).all()
    # the same seed sends the same windows and baselines; the baseline is
    # a stream of its own, not the current window's
    a = series.draw_sweep(7, 3, fl.groups, (fl.group_of, fl.local), fl.w, fl.n_hist, fl.fam, cell.traffic)
    b = series.draw_sweep(7, 3, fl.groups, (fl.group_of, fl.local), fl.w, fl.n_hist, fl.fam, cell.traffic)
    assert all((x == y).all() for x, y in zip(a[0], b[0])) and (a[2][canary] == b[2][canary]).all()
    assert [x is None for x in a[2]] == [not g.get("baseline_window") for g in fl.groups]
    assert not (a[2][canary] == a[0][canary]).all()
    # two groups of one alias count do not send each other's windows
    uni = next(i for i, g in enumerate(fl.groups) if g["kind"] == "univariate")
    n = min(len(a[0][uni]), len(a[0][canary]))
    assert not np.allclose(a[0][uni][:n], a[0][canary][:n])


# -- the comparison's own arithmetic -----------------------------------------


def test_margin_is_a_lower_bound_on_what_flips_the_rule():
    from chipbench.references.lstm import hybrid_flags, point_margins

    rng = np.random.default_rng(0)
    k, w = 500, 12
    a = np.exp(rng.normal(-0.5, 0.8, (k, w)))
    r = np.exp(rng.normal(-0.3, 0.8, (k, w)))
    h = r * 0.7
    valid = np.ones(k, bool)
    sc = {"a": a, "r": r, "h": h, "r1": np.full((k, w), 1e-3), "valid": valid}
    sc["flags"] = hybrid_flags(a, r, h, valid)
    m = point_margins(sc)
    scale = m.min(axis=1, keepdims=True) * 0.999
    for _ in range(50):
        d = rng.uniform(-1, 1, (3, k, w)) * scale
        moved = hybrid_flags(a * np.exp(d[0]), r * np.exp(d[1]), h * np.exp(d[2]), valid)
        assert (moved == sc["flags"]).all()
    ref = {"flags": sc["flags"], "margins": m}
    assert compare.flip_margin(sc["flags"], ref)[:2] == (0.0, 0)
    flipped = sc["flags"].copy()
    flipped[0, 0] = ~flipped[0, 0]
    assert compare.flip_margin(flipped, ref)[0] == pytest.approx(m[0, 0])


def test_payload_is_held_to_what_was_sent():
    times = 1000 + 60 * np.arange(4)
    sent = np.arange(8, dtype=np.float32).reshape(2, 4)
    row = {"status": compare.ANOMALY_STATUS, "reason": compare.ANOMALY_REASON, "sent": sent,
           "info": {"values": {"a": [1060.0, 1.0], "b": [1060.0, 5.0]}}}
    flags, err, bad = compare.program_flags(row, ["a", "b"], times)
    assert flags.tolist() == [False, True, False, False] and err == 0.0 and bad == 0
    row["info"]["values"]["b"] = [1060.0, 5.5]
    assert compare.program_flags(row, ["a", "b"], times)[1] == pytest.approx(0.5)
    row["info"]["values"]["b"] = [999.0, 5.0]
    assert compare.program_flags(row, ["a", "b"], times)[1] == compare.BROKEN
    row["status"] = "completed_unknown"
    assert compare.program_flags(row, ["a", "b"], times)[2] == 1


# -- byte reckonings against the program's own templates ---------------------


def test_row_bytes_match_the_program():
    import jax

    from foremast_tpu.engine import arena
    from foremast_tpu.engine.multivariate import MultivariateJudge

    assert bytes_model.lstm_row_bytes(4, 1440) == 61_585
    assert bytes_model.lstm_row_bytes(6, 1440) == 75_529
    assert bytes_model.univariate_row_bytes(1440) == 5_780 == arena._row_bytes(1440)
    assert bytes_model.univariate_row_bytes(1) == 24 == arena._row_bytes(1)
    for f in (3, 4, 6):
        tmpl = MultivariateJudge()._lstm_template(f, 1440)
        want = sum(
            int(np.prod(leaf.shape)) * leaf.dtype.itemsize for leaf in jax.tree.leaves(tmpl)
        )
        assert bytes_model.lstm_row_bytes(f, 1440) == want
    bi = MultivariateJudge()._bi_template()
    assert bytes_model.bivariate_row_bytes() == sum(
        int(np.prod(leaf.shape)) * leaf.dtype.itemsize for leaf in jax.tree.leaves(bi)
    )
    mixed = _config("mixed-auto-daily")
    assert mixed["row_bytes"] == {
        "lstm": bytes_model.lstm_row_bytes(4, 1440),
        "univariate": arena._row_bytes(1440),
        "bivariate": bytes_model.bivariate_row_bytes(),
    }
    # every group of the mixed fleet names the function that counts its
    # operations, and each kind's dispatch bytes hold its rows' state
    for g in mixed["fleet"]:
        module, fn = g["flops_fn"].rsplit(".", 1)
        assert getattr(bytes_model, fn)(len(g["aliases"]), 32) > 0 and module == "bytes_model"
    b = 4096
    assert bytes_model.univariate_score_bytes(b, 1, 32, 1440) > b * arena._row_bytes(32)
    assert bytes_model.canary_score_bytes(b, 1, 32, 1440) > bytes_model.univariate_score_bytes(b, 1, 32, 1440)
    assert bytes_model.bivariate_score_bytes(b, 2, 32, 1440) > b * bytes_model.bivariate_row_bytes()
    cfg = _config("hybrid4-daily")
    assert cfg["row_bytes"] == 61_585
    # the fleet fills whole slices (no pad row) and a power-of-two arena
    # (no reserved rows): live rows = capacity = 1.88 GiB
    services = cfg["fleet"][0]["services"]
    assert services == cfg["services"] == 32_768
    assert services % int(cfg["env"]["FOREMAST_SWEEP_SLICE_DOCS"]) == 0
    assert services & (services - 1) == 0


# -- the trace reduction ------------------------------------------------------


def test_union_and_gaps():
    u = tracered.union([[5, 7], [0, 2], [1, 3], [7, 9]])
    assert u == [[0, 3], [5, 9]]
    gaps = tracered._gaps(u, (0, 12), [
        {"name": "worker.fetch", "ts": 3.2e-3, "dur": 1.5e-3, "args": {"stage": "metric_fetch"}},
    ], lambda wall_s: wall_s * 1e9 / 1e6 * 1e6 / 1e6, 10)
    assert sum(v for _k, v in gaps) == pytest.approx(5e-9)
    assert tracered.module_name("jit_lstm_joint_score_from_rows(1234567)") == "jit_lstm_joint_score_from_rows"


def test_reduction_of_the_recorded_trace():
    path = os.path.join(ROOT, "chipbench", "testdata", "v5e_small.xplane.pb")
    want_path = os.path.join(ROOT, "chipbench", "testdata", "v5e_small.expected.json")
    if not os.path.exists(path):
        pytest.skip("no recorded trace yet")
    want = json.load(open(want_path))
    red = tracered.reduce(tracered.load(path))
    assert red["devices_traced"] == want["devices_traced"]
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    for name, rec in want["modules"].items():
        assert red["modules"][name]["count"] == rec["count"]
        assert red["modules"][name]["seconds"] == pytest.approx(rec["seconds"], rel=1e-9)
    assert 0 < red["busy_s"]


# -- data-driven: new files and entries alone add a cell ----------------------


_STAND_IN_REFERENCE = '''"""A stand-in reference written by the test: it flags nothing and holds
no point by any margin, so no disagreement with it counts."""
import numpy as np


def judge(rows, group, cfg, history, control=False, log=None):
    assert history(rows[0]["uid"]).shape == (len(group["aliases"]), cfg["history_points"])
    if group.get("baseline_window"):
        assert all(r["base"].shape == (1, group["baseline_window"]["points"]) for r in rows)
    k, w = len(rows), rows[0]["sent"].shape[-1]
    return {"flags": np.zeros((k, w), bool), "margins": np.zeros((k, w), np.float32)}
'''

_STAND_IN_FAULTS = '''import contextlib

FAULTS = {"none": (contextlib.nullcontext, "unjudged")}
'''


def test_new_cell_config_mix_metric_and_reader_need_no_edit(tmp_path):
    """A later PR's cell, by files and entries alone: a configuration of
    FOUR groups (4-alias, 2-alias, 1-alias and 1-alias with a baseline; the
    new kinds under names of their own, with stand-in references and faults
    the test writes), a mix, a metric and a reader. It runs `--tiny` through
    BrainWorker on the CPU, and the LSTM group's judgments have to agree
    with references/lstm.py: they do only where each LSTM doc's place in its
    fit batch is its rank among the LSTM docs of its cold chunk."""
    root = tmp_path / "repo"
    shutil.copytree(
        os.path.join(ROOT, "chipbench"), root / "chipbench",
        ignore=shutil.ignore_patterns("__pycache__", "testdata"),
    )
    bench = json.loads(json.dumps(BENCH))
    cfg = _config("hybrid4-daily")
    cfg["name"] = "four-kinds"
    lstm = cfg["fleet"][0]
    cfg["tiny"]["fleet"] = [
        dict(lstm, services=40),
        {"kind": "pairx", "aliases": ["latency", "tps"], "services": 24},
        {"kind": "solo", "aliases": ["cpu"], "services": 20},
        {"kind": "solob", "aliases": ["latency"], "services": 12,
         "baseline_window": {"points": 30, "shift_share": 0.2, "shift": 0.15}},
    ]
    cfg["fleet"] = [dict(g, services=g["services"] * 64) for g in cfg["tiny"]["fleet"]]
    json.dump(cfg, open(root / "chipbench" / "configs" / "four-kinds.json", "w"))
    for kind in ("pairx", "solo", "solob"):
        (root / "chipbench" / "references" / f"{kind}.py").write_text(_STAND_IN_REFERENCE)
        (root / "chipbench" / "faults" / f"{kind}.py").write_text(_STAND_IN_FAULTS)
    mix = json.load(open(os.path.join(ROOT, "chipbench", "traffic", "warm-sweep.json")))
    mix["spike_doc_share"] = 0.01
    json.dump(mix, open(root / "chipbench" / "traffic" / "stormy.json", "w"))
    (root / "chipbench" / "readers" / "doc_ticks.py").write_text(
        "def read(record, params):\n    return record['doc_ticks'] * params['scale']\n"
    )
    json.dump(
        {"name": "doc_ticks.sweep", "unit": "count", "better": "higher", "layer": "job plane jobs/worker.py",
         "moves": "windows_per_s", "source": "program_counter", "reader": "doc_ticks", "params": {"scale": 2}},
        open(root / "chipbench" / "layers" / "doc_ticks.sweep.json", "w"),
    )
    bench["configs"].append({"name": "four-kinds", "source": "x", "reduced": [],
                             "file": "chipbench/configs/four-kinds.json", "why": "x"})
    bench["workloads"].append({"name": "four-kinds.stormy", "config": "four-kinds",
                               "traffic": "stormy", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "doc_ticks.sweep", "unit": "count", "better": "higher",
                               "source": "program_counter", "layer": "job plane jobs/worker.py",
                               "moves": "windows_per_s", "workloads": ["four-kinds.stormy"]})
    json.dump(bench, open(root / "BENCHMARK.json", "w"))
    cell = spec.Cell("four-kinds.stormy", root=str(root))
    assert [len(g["aliases"]) for g in cell.sized(True)["fleet"]] == [4, 2, 1, 1]
    assert cell.traffic["spike_doc_share"] == 0.01
    assert [m["name"] for m in cell.per_layer] == ["doc_ticks.sweep"]
    # the cells that were there are untouched by the additions
    assert [m["name"] for m in spec.Cell(CELLS[0], root=str(root)).per_layer] == [
        m["name"] for m in spec.Cell(CELLS[0]).per_layer
    ]
    # the copy is run as a checkout of its own (its chipbench first on the
    # path, the program from the repo behind it)
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "four-kinds.stormy", "--seed", "31",
         "--seconds", "2", "--trace", "1", "--tiny"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=f"{root}{os.pathsep}{ROOT}"),
        capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    numbers = line["numbers"]
    assert line["correct"] is True and line["failed"] == 0, numbers
    sweeps = numbers["compared.lstm"][0] / 40
    assert sweeps >= 1 and numbers["compared"][0] == 96 * sweeps
    for kind, n in (("pairx", 24), ("solo", 20), ("solob", 12)):
        assert numbers["compared." + kind][0] == n * sweeps
    assert numbers["flip_rate.lstm"][0] <= cell.config["correct_limits"]["flip_rate"]
    assert line["metrics"]["doc_ticks.sweep"] == {"value": 2.0 * 96 * sweeps, "unit": "count"}


def test_benchmark_json_keeps_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    assert all(0.01 <= m["bound"] <= 0.1 for m in BENCH["end_to_end"])
    for m in BENCH["per_layer"]:
        layer = spec.layer_metric(m["name"])
        for key in ("unit", "better", "source", "layer", "moves"):
            assert layer[key] == m[key], (m["name"], key)
        assert m["moves"] in e2e
        assert os.path.exists(os.path.join(ROOT, "chipbench", "readers", layer["reader"] + ".py"))
    for c in BENCH["configs"]:
        assert c["file"].startswith("chipbench/") and os.path.exists(os.path.join(ROOT, c["file"]))
    # a why, a layer and a source: 1 to 200 characters on one line
    said = [c[k] for c in BENCH["configs"] for k in ("source", "why")]
    said += [w["why"] for w in BENCH["workloads"]] + [m["layer"] for m in BENCH["per_layer"]]
    assert all(1 <= len(s) <= 200 and "\n" not in s and "\t" not in s for s in said)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(BENCH["workloads"]) // 4)


# -- the warm joint program compiles for the chip at the cell's real B --------


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_warm_joint_program_compiles_for_v5e_at_real_size(topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from foremast_tpu.engine.multivariate import (
        MultivariateJudge,
        lstm_joint_score_from_rows,
    )

    cfg = _config("hybrid4-daily")
    f = len(cfg["fleet"][0]["aliases"])
    b = int(cfg["env"]["FOREMAST_SWEEP_SLICE_DOCS"])
    cap = cfg["fleet"][0]["services"]
    one = SingleDeviceSharding(topo.devices[0])
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    tmpl = MultivariateJudge()._lstm_template(f, cfg["season_steps"])
    state = jax.tree.map(lambda leaf: sd((cap,) + leaf.shape, leaf.dtype), tmpl)
    tc = 32
    args = (
        state, sd((b,), jnp.int32), sd((b, 1, tc, f), jnp.float32), sd((b, tc), jnp.bool_),
        sd((b,), jnp.float32), sd((b,), jnp.float32), sd((b,), jnp.float32), sd((b,), jnp.int32),
    )
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = lstm_joint_score_from_rows.lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    mem = compiled.memory_analysis()
    arena_bytes = cap * cfg["row_bytes"]
    assert mem.argument_size_in_bytes >= arena_bytes
    # the whole program fits a 16 GB chip beside the arena it reads
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes
    assert total < 15.5e9, total
