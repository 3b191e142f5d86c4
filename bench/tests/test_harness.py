"""The harness finds everything by name, refuses to measure off the chip,
and prints the result line the contract asks for."""
import json
import os
import shutil
import types

import pytest

from bench import harness, run
from bench.tests import tiny

REPO = tiny.REPO
PROBE = os.path.join(REPO, "bench", "testdata", "probe.xplane.pb")


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("workload", [w["name"]
                                      for w in _bench()["workloads"]])
def test_every_cell_resolves_its_files_by_name(workload):
    cell = harness.find_cell(REPO, workload)
    driver = harness.load_module(REPO, "drivers", cell.traffic["driver"])
    assert callable(driver.run)
    for kind in ("reference", "flops"):
        harness.load_module(REPO, kind, cell.cfg["family"])
    for m in cell.metrics("per_layer"):
        assert callable(harness.load_module(REPO, "metrics", m["name"]).read)
    limits = harness.limits_of(cell)
    assert limits and all(v["lower"] < v["limit"] < v["upper"]
                          for v in limits.values())
    assert cell.metrics("end_to_end")[-1]["name"] == "setup_s"


def _probe_dir(tmp_path):
    d = tmp_path / "trace" / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    shutil.copy(PROBE, d / "probe.xplane.pb")
    return str(tmp_path / "trace")


def test_a_new_metric_file_is_picked_up_without_editing_any(tmp_path):
    root = tiny.make(str(tmp_path / "root"))
    with open(os.path.join(root, "bench", "metrics", "extra.train.py"),
              "w") as fh:
        fh.write("def read(run):\n"
                 "    return run.summary.kernel_n['ghost_norm']\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    bench["per_layer"].append({
        "name": "extra.train", "unit": "1", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "train_tokens_per_s", "workloads": [tiny.CELL]})
    with open(path, "w") as fh:
        json.dump(bench, fh)
    cell = harness.find_cell(root, tiny.CELL)
    ctx = types.SimpleNamespace(
        trace_dir=_probe_dir(tmp_path),
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite")])
    stats = {"steps": 3, "host_s": {"batch": 0.003}, "choices": []}
    metrics, summary = run.per_layer(cell, ctx, stats, root)
    assert metrics["extra.train"] == {"value": 3, "unit": "1"}
    assert metrics["host_ms_per_step.train"]["value"] == pytest.approx(1.0)
    assert summary.busy_s > 0


def test_refuses_a_run_without_a_tpu(tmp_path, capsys):
    root = tiny.make(str(tmp_path))
    rc = run.main(["--workload", tiny.CELL, "--seed", "1", "--seconds", "1"],
                  root=root, cache=False)
    out = capsys.readouterr()
    assert rc == 2 and out.out == "" and "no TPU" in out.err


def test_refuses_a_checkout_without_the_program(tmp_path, capsys):
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    rc = run.main(["--workload", _bench()["workloads"][0]["name"], "--seed",
                   "1", "--seconds", "1"], root=str(tmp_path), cache=False)
    assert rc == 2 and capsys.readouterr().out == ""


@pytest.mark.parametrize("traced", [0, 1])
def test_the_last_line_has_exactly_the_contract_keys(tmp_path, capsys,
                                                     traced):
    root = tiny.make(str(tmp_path))
    rc = run.main(["--workload", tiny.CELL, "--seed", str(2 ** 40 + 3),
                   "--seconds", "0.5", "--trace", str(traced)],
                  root=root, allow_cpu=True, cache=False)
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    keys += ["breakdown", "compared"] if traced else ["compared"]
    assert rc == 0 and list(line) == keys
    assert line["correct"] is True and line["attempted"] > 0
    assert set(line["compared"]) == {"loss_gap", "clip_gap", "grad_gap",
                                     "change_gap"}
    tail = out.err.strip().splitlines()[-5:]
    assert tail[0] == "correct True; compared with their limits:"
    assert [t.split()[0] for t in tail[1:]] == list(line["compared"])
    if traced:
        # a CPU run reports no device metric
        assert line["metrics"] == {"host_ms_per_step.train":
                                   line["metrics"]["host_ms_per_step.train"]}
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
