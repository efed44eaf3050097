"""Whole runs at rehearsal size on the CPU: every cell end to end, the
refusal without a chip, the faults and the control that ``correct`` has
to catch, and a benchmark that grows by files alone."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.tests.conftest import ROOT

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
DDP_CELLS = [w["name"] for w in BENCH["workloads"] if w["chips"] > 1]


def command(*args, cwd=ROOT, env=None):
    return subprocess.run(
        [sys.executable, *BENCH["command"][1:], *args], cwd=cwd,
        env=dict(os.environ, **(env or {})), capture_output=True, text=True,
        timeout=900)


def in_process(capsys, cell, seed, step_wrapper=None, trace=0):
    from benchmark import run
    assert run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                     "0.2", "--trace", str(trace), "--rehearse"],
                    step_wrapper=step_wrapper) == 0
    out, err = capsys.readouterr()
    return json.loads(out.strip().splitlines()[-1]), out, err


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_the_cell_and_reports_no_device_metric(cell):
    done = command("--workload", cell, "--seed", "2147483777", "--seconds",
                   "0.3", "--trace", "1", "--rehearse")
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert line["device"]["platform"] == "cpu"        # no chip run's line
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 2 and line["failed"] == 0
    assert list(line)[-1] == "compared"
    for row in line["compared"].values():
        assert set(row) == {"value", "limit"}
    # what the contract has no key for is on the earlier lines
    for words in ("compile cache:", "Mosaic kernels in the step:",
                  "intervals behind step_ms_p95",
                  "compilations inside the window: 0", "the step waited",
                  "final loss scale", "first losses:"):
        assert words in done.stdout, words
    assert done.stderr.strip().splitlines()[-1] == "correct: True"
    assert "compared grad_gap:" in done.stderr


def test_without_a_chip_there_is_no_result_line():
    done = command("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0", env={"JAX_PLATFORMS": "cpu"})
    assert done.returncode != 0
    assert "no accelerator" in done.stderr
    assert not any(line.startswith("{") for line in done.stdout.splitlines())


def unchanged_state(step):
    return lambda state, *batch: (state, step(state, *batch)[1])


def half_of_the_batch(step):
    return lambda state, *batch: step(
        state, *(x[:x.shape[0] // 2] for x in batch))


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [unchanged_state, half_of_the_batch],
                         ids=lambda f: f.__name__)
def test_a_broken_step_comes_out_not_correct(capsys, cell, fault):
    line, _, err = in_process(capsys, cell, 2147483999, step_wrapper=fault)
    assert line["correct"] is False
    assert "correct: False" in err


@pytest.mark.parametrize("cell", DDP_CELLS)
def test_the_exchange_left_out_comes_out_not_correct(capsys, monkeypatch,
                                                     cell):
    """Every chip follows chip 0's rows alone: no mean over the replicas."""
    import jax

    from apex_tpu.parallel import distributed

    def chip0_only(grads, axis_name, config=None):
        first = jax.lax.axis_index(axis_name) == 0
        return jax.tree.map(
            lambda g: jax.lax.psum(jax.numpy.where(first, g, 0), axis_name),
            grads)

    monkeypatch.setattr(distributed, "reduce_gradients", chip0_only)
    line, _, _ = in_process(capsys, cell, 2147484001)
    assert line["correct"] is False


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_comes_out_not_correct(cell):
    """The reference in float8 in the program's place, at the widest size
    a test run holds, fails the cell's own limits."""
    import jax
    import numpy as np

    from benchmark import run
    from benchmark.drivers import train
    from benchmark.reference import common, train as ref

    _, c, cfg, family, _ = run.resolve(cell, rehearse=True)
    cfg.update(cfg.get("control_test", {}))
    traffic = dict(c["parameters"], **c.get("control_test", {}))
    rng = np.random.default_rng(11)
    rows = traffic["rows_per_chip"] * c["chips"]
    batches = [family.make_batch(rng, rows, cfg, traffic) for _ in range(3)]
    kw = train.reference_kwargs(cfg, traffic, jax.devices()[:1])
    spec = family.reference.param_spec(cfg)
    want = ref.follow(family.reference, cfg, spec, 11, batches, **kw)
    control = ref.follow(family.reference, cfg, spec, 11, batches,
                         q=common.fp8_operands, **kw)
    correct, table = run.judge(
        {k: v for k, v in ref.gaps(control, want).items()
         if k.endswith("_gap")}, c["limits"])
    assert correct is False, table


def test_a_cell_a_configuration_a_driver_and_a_metric_are_added_by_files(
        tmp_path):
    """Nothing that is there is edited: files are added, and entries to
    ``BENCHMARK.json``.  The cell added is the staged BERT cell (its
    files are in the tree; PERF.md says why ``BENCHMARK.json`` does not
    list it yet), under a driver and with a metric that are new files."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    here = tmp_path / "benchmark"
    cfg = json.loads((here / "configs/bert-large-uncased.json").read_text())
    cell = json.loads((here / "workloads/bert_large.pretrain_b16_s512.json")
                      .read_text())
    cell.update(name="bert_large.again", traffic="again",
                driver="train_again")
    (here / "workloads/bert_large.again.json").write_text(json.dumps(cell))
    (here / "drivers/train_again.py").write_text(
        "from benchmark.drivers.train import run  # noqa: F401\n")
    (here / "metrics/steps.traced.json").write_text(json.dumps({
        "name": "steps.traced", "unit": "steps", "better": "higher",
        "source": "device_trace", "layer": "device", "moves": "tokens_per_s",
        "reader": "steps_traced", "workloads": ["bert_large.again"]}))
    (here / "readers/steps_traced.py").write_text(
        "def read(run):\n    return run.steps_traced or None\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": cfg["name"], "source": cfg["source"],
        "file": "benchmark/configs/bert-large-uncased.json",
        "reduced": [], "why": "a test"})
    bench["workloads"].append({k: cell[k] for k in (
        "name", "config", "traffic", "chips", "why")})
    bench["per_layer"].append({
        "name": "steps.traced", "unit": "steps", "better": "higher",
        "source": "device_trace", "layer": "device", "moves": "tokens_per_s",
        "workloads": ["bert_large.again"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    done = command("--workload", "bert_large.again", "--seed", "5",
                   "--seconds", "0.2", "--trace", "0", "--rehearse",
                   cwd=tmp_path, env={"PYTHONPATH": ROOT})
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["attempted"] > 2 and line["rehearsal"] is True
    assert set(line["compared"]) == {"loss_gap", "grad_gap", "delta_gap"}

    # the new metric's reader is found by name and read in its cell only
    probe = (
        "import json, types\n"
        "from benchmark import run\n"
        "bench = json.load(open('BENCHMARK.json'))\n"
        "fake = types.SimpleNamespace(steps_traced=6, tokens_per_s=None, "
        "kernel_seconds={}, instruction_seconds={}, busy_s0=None, chips=1, "
        "exposed_collective_s=None, busy_by_chip=[], memory_peak_bytes=None, "
        "compile_warm_s=None)\n"
        "for cell in ('bert_large.again', 'gpt2_medium.lm_b8_s1024'):\n"
        "    print(sorted(run.read_per_layer(bench, {'name': cell}, fake, "
        "root='.')))\n")
    done = subprocess.run([sys.executable, "-c", probe], cwd=tmp_path,
                          capture_output=True, text=True, timeout=300,
                          env=dict(os.environ, PYTHONPATH=str(tmp_path)))
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.splitlines() == ["['steps.traced']", "[]"]
