"""Whole runs at rehearsal size on the CPU: every cell end to end, the
refusal without a chip, the faults and the control that ``correct`` has
to catch, a cell that asks for no recipe and gets the step it always
got, and a benchmark that grows by files alone, a family with a rule of
its own among them."""

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
    assert set(line["compared"]) == {"loss_gap", "grad_gap",
                                     "grad_gap_median", "delta_gap"}

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


PLAIN_CELLS = [c for c in CELLS if not {"lr_warmup_steps", "balance_rate"}
               & set(json.load(open(os.path.join(
                   ROOT, "benchmark", "workloads", c + ".json")))
                   ["parameters"])]


@pytest.mark.parametrize("cell", PLAIN_CELLS)
def test_a_cell_that_asks_for_no_recipe_gets_the_step_it_always_got(
        monkeypatch, cell):
    """Neither the schedule nor the stateful step is reached, the
    optimizer gets the configuration's plain numbers, and (one chip) the
    step lowers to the text of the step built the way it was before
    there was a recipe: ``amp.make_train_step`` of the family's loss."""
    import jax
    import numpy as np

    from apex_tpu import amp, optimizers
    from benchmark import run, weights
    from benchmark.drivers import train

    def never(*args, **kwargs):
        raise AssertionError("a cell without the keys reached the recipe")

    monkeypatch.setattr(train, "warmup", never)
    monkeypatch.setattr(train, "stateful", never)
    _, c, cfg, family, _ = run.resolve(cell, rehearse=True)
    assert not hasattr(family, "step_state") \
        or family.step_state(cfg, c["parameters"]) is None
    made = train.make_step(c, cfg, family, jax.devices()[:c["chips"]])
    assert made["hook"] is None
    kw = train.reference_kwargs(cfg, c["parameters"], None)
    assert "lr_warmup_steps" not in kw["opt_kwargs"]
    if c["chips"] != 1:
        return
    opt = cfg["optimizer"]
    a = amp.initialize(optimizer=getattr(optimizers, opt["program"])(
        **dict(opt["args"], betas=tuple(opt["args"]["betas"]))),
        opt_level=cfg["opt_level"], verbosity=0)
    before = amp.make_train_step(a, family.program_loss(cfg, c["parameters"]))
    state = jax.eval_shape(lambda k: a.init(weights.make(made["spec"], k)),
                           weights.seed_key(1))
    batch = family.make_batch(np.random.default_rng(0),
                              c["parameters"]["rows_per_chip"], cfg,
                              c["parameters"])

    def text(step):
        return jax.jit(step, donate_argnums=(0,)).lower(
            state, *batch).as_text()

    assert text(made["step_fn"]) == text(before)


OWN_FAMILY = '''
"""A family added by files alone: the DeepSeek-V3 family's model and
counters, a warm-up, and a balance rule of its own (in proportion to an
expert's excess over the mean, at the cell's ``own_rate``)."""
from benchmark.families import deepseek_v3 as _base
from benchmark.families.deepseek_v3 import *  # noqa: F401,F403
from benchmark.reference import deepseek_own as reference  # noqa: F401


def step_state(cfg, traffic):
    kept = _base.step_state(cfg, dict(traffic, balance_rate=1.0))
    return dict(kept, update=reference.own_update(kept["paths"],
                                                  traffic["own_rate"],
                                                  lambda aux: aux["counts"]),
                describe=lambda steps: f"a rule of its own over "
                                       f"{len(steps)} steps")
'''

OWN_REFERENCE = '''
"""The plain reference of ``families/deepseek_own.py``."""
import jax.numpy as jnp

from benchmark.reference import deepseek_v3 as _base
from benchmark.reference.deepseek_v3 import *  # noqa: F401,F403


def own_update(paths, rate, counts_of):
    def update(values, read):
        counts = counts_of(read).astype(jnp.float32)
        excess = counts / jnp.mean(counts, axis=-1, keepdims=True) - 1.0
        return {p: values[p] - rate * excess[i] for i, p in enumerate(paths)}
    return update


def step_state(cfg, traffic):
    kept = _base.step_state(cfg, dict(traffic, balance_rate=1.0))
    return dict(kept, update=own_update(kept["paths"], traffic["own_rate"],
                                        lambda counts: counts))
'''


def test_a_family_with_a_rule_of_its_own_is_added_by_files(tmp_path):
    """What a later ``model_config`` PR needs: a new family asks for the
    warm-up through its cell's parameters and brings the update of its
    own state through ``step_state`` on both sides, and no file that is
    there is edited.  ``correct`` holds the two sides' rule to each
    other (``state_gap``): a program that left it out would read 1."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    here = tmp_path / "benchmark"
    (here / "families/deepseek_own.py").write_text(OWN_FAMILY)
    (here / "reference/deepseek_own.py").write_text(OWN_REFERENCE)
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    listed = [c for c in bench["configs"]
              if c["name"].startswith("kanana")][0]
    cfg = json.loads((tmp_path / listed["file"]).read_text())
    cfg.update(name="deepseek-own", family="deepseek_own")
    (here / "configs/deepseek-own.json").write_text(json.dumps(cfg))
    cell = json.loads((here / "workloads" / (
        [w["name"] for w in bench["workloads"]
         if w["config"] == listed["name"]][0] + ".json")).read_text())
    cell.update(name="deepseek_own.lm", config="deepseek-own", traffic="lm")
    del cell["parameters"]["balance_rate"]
    cell["parameters"].update(lr_warmup_steps=100, own_rate=0.002)
    (here / "workloads/deepseek_own.lm.json").write_text(json.dumps(cell))
    bench["configs"].append(dict(listed, name="deepseek-own",
                                 file="benchmark/configs/deepseek-own.json"))
    bench["workloads"].append({k: cell[k] for k in (
        "name", "config", "traffic", "chips", "why")})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    done = command("--workload", "deepseek_own.lm", "--seed", "7",
                   "--seconds", "0.2", "--trace", "0", "--rehearse",
                   cwd=tmp_path, env={"PYTHONPATH": ROOT})
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert "step state: a rule of its own over" in done.stdout
    assert line["correct"] is True, line["compared"]
    gap = line["compared"]["state_gap"]
    assert gap["limit"] is not None and 0.0 < gap["value"] < 0.1, gap
