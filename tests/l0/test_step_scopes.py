"""The compiled train step names its own phases and blocks.

Three tiny steps are compiled on the CPU: GPT O2 + FusedAdam alone, the
same under ``shard_map`` with ``DistributedDataParallel`` on four virtual
devices, and BERT O2 + FusedLAMB.  Their ``compiled.as_text()`` has to
carry every scope of ``apex_tpu/utils/profiling.py`` that the step
opens, the benchmark's copy of the names (``benchmark/scopes.py``) has
to equal the program's, and ``benchmark.scopes.phase`` has to reach
nearly every operation the program traced.  A scope is metadata: the
instructions themselves must not move.
"""

import collections
import contextlib
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import amp, optimizers
from apex_tpu.utils import profiling

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
from benchmark import scopes, trace  # noqa: E402

#: opcodes that hold or name a value and do no work
NO_WORK = ("parameter", "constant", "get-tuple-element", "tuple", "bitcast",
           "broadcast")


def gpt_step(data_parallel: bool):
    from apex_tpu.models.gpt import GPTModel, gpt_tiny, lm_loss
    cfg = gpt_tiny()
    model = GPTModel(cfg)
    ids = jnp.zeros((4, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    a = amp.initialize(optimizer=optimizers.FusedAdam(lr=1e-3),
                       opt_level="O2", verbosity=0)

    def loss_fn(p, ids):
        logits = model.apply({"params": p}, ids)
        return lm_loss(logits[:, :-1], ids[:, 1:])

    if not data_parallel:
        return jax.jit(amp.make_train_step(a, loss_fn)), a.init(params), ids
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from apex_tpu.parallel import DistributedDataParallel
    inner = amp.make_train_step(
        a, loss_fn, axis_name="data",
        reduce_fn=DistributedDataParallel(axis_name="data").reduce)

    def sharded(state, ids):
        state, m = inner(state, ids)
        return state, dict(m, loss=jax.lax.pmean(m["loss"], "data"))

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    return (jax.jit(shard_map(sharded, mesh=mesh, in_specs=(P(), P("data")),
                              out_specs=(P(), P()))),
            a.init(params), ids)


def bert_step():
    from apex_tpu.models.bert import (
        BertForPreTraining, bert_tiny, pretraining_loss)
    model = BertForPreTraining(bert_tiny())
    ids = jnp.zeros((4, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    a = amp.initialize(optimizer=optimizers.FusedLAMB(lr=1e-3),
                       opt_level="O2", verbosity=0)

    def loss_fn(p, ids, labels, nsp, mask):
        mlm, nsp_logits = model.apply({"params": p}, ids)
        return pretraining_loss(mlm, nsp_logits, labels, nsp, mask)

    return (jax.jit(amp.make_train_step(a, loss_fn)), a.init(params), ids,
            ids, jnp.zeros((4,), jnp.int32), jnp.ones((4, 16), jnp.float32))


#: step -> (builder, the scopes its compiled text has to carry)
STEPS = {
    "gpt": (lambda: gpt_step(False),
            ("amp_cast", "amp_unscale", "amp_scaler_update",
             "amp_optimizer_step", "mlp", "lm_loss")),
    "gpt_ddp4": (lambda: gpt_step(True),
                 ("amp_cast", "amp_reduce", "amp_unscale",
                  "amp_scaler_update", "amp_optimizer_step", "mlp",
                  "lm_loss", "ddp_allreduce")),
    "bert": (bert_step,
             ("amp_cast", "amp_unscale", "amp_scaler_update",
              "amp_optimizer_step", "mlp", "pretraining_loss")),
}
FAMILY = {"gpt": "gpt", "gpt_ddp4": "gpt", "bert": "bert"}


@pytest.fixture(scope="module")
def compiled_text():
    texts = {}

    def get(which: str) -> str:
        if which not in texts:
            step, *args = STEPS[which][0]()
            texts[which] = step.lower(*args).compile().as_text()
        return texts[which]

    return get


def test_the_benchmark_copy_of_the_names_equals_the_programs():
    assert scopes.TRAIN_STEP_SCOPES == profiling.TRAIN_STEP_SCOPES
    assert scopes.MODEL_SCOPES == profiling.MODEL_SCOPES


def test_the_names_are_defined_once_in_the_program():
    """A second spelling of a phase scope inside ``apex_tpu`` would drift
    from the tuple the readers match."""
    spelled = [(name, str(path.relative_to(REPO)))
               for path in (REPO / "apex_tpu").rglob("*.py")
               for name in profiling.TRAIN_STEP_SCOPES
               if f'"{name}"' in path.read_text()]
    assert sorted(spelled) == sorted(
        (name, "apex_tpu/utils/profiling.py")
        for name in profiling.TRAIN_STEP_SCOPES)


def test_every_name_is_opened_by_some_step():
    opened = {name for _, names in STEPS.values() for name in names}
    assert set(scopes.TRAIN_STEP_SCOPES + scopes.MODEL_SCOPES) <= opened


@pytest.mark.parametrize("which,name", [
    (which, name) for which, (_, names) in STEPS.items() for name in names])
def test_scope_is_in_the_compiled_step(compiled_text, which, name):
    names = trace.op_names(compiled_text(which)).values()
    on_paths = {seg for op_name in names for seg in scopes.segments(op_name)}
    assert name in on_paths


@pytest.mark.parametrize("which", list(STEPS))
def test_no_loss_operation_has_an_empty_scope(compiled_text, which):
    """Before the loss functions opened a scope their operations came out
    as ``jvp()/exp``, ``transpose(jvp())/div``: indistinguishable from
    anything else outside a flax module."""
    names = list(trace.op_names(compiled_text(which)).values())
    loss = "lm_loss" if FAMILY[which] == "gpt" else "pretraining_loss"
    under_loss = {n.rsplit("/", 1)[-1] for n in names
                  if loss in scopes.segments(n)}
    assert {"exp", "log", "reduce_max"} <= under_loss
    empty = {n.rsplit("/", 1)[-1] for n in names
             if "jvp()/" in n}
    assert not empty & {"exp", "log", "reduce_max", "reduce_sum", "div",
                        "gather", "scatter-add"}, empty


@pytest.mark.parametrize("which", list(STEPS))
def test_the_feed_forward_sits_under_mlp(compiled_text, which):
    names = list(trace.op_names(compiled_text(which)).values())
    ffn = [n for n in names if re.search(r"/ffn_(in|out)/", n)]
    assert len(ffn) >= 8
    assert all(re.search(r"/mlp/ffn_(in|out)/", n) for n in ffn)
    # the activation too, and forward as well as backward
    assert any(re.search(r"/jvp\(\w+\)/.*/mlp/tanh$", n) for n in names)
    assert any("transpose(jvp(" in n and "/mlp/" in n for n in names)
    assert all(scopes.block(n, FAMILY[which]) == "mlp" for n in ffn)


@pytest.mark.parametrize("which", list(STEPS))
def test_phase_reaches_the_operations_the_program_traced(compiled_text,
                                                         which):
    """Of the instructions that do work and that the program traced (their
    ``op_name`` starts at the jitted function), under 5% fall into no
    phase; and each phase the step has is found."""
    text = compiled_text(which)
    names = trace.op_names(text)
    opcodes = {}
    for line in text.splitlines():
        d = trace._HLO_DEF.match(line)
        if d:
            opcodes[d.group(1)] = trace.opcode(line.strip())
    phases = collections.Counter(
        scopes.phase(instr, op_name) for instr, op_name in names.items()
        if op_name.startswith("jit(") and opcodes[instr] not in NO_WORK)
    assert set(phases) <= set(scopes.PHASES)
    assert phases["unscoped"] < 0.05 * sum(phases.values()), phases
    expected = {"forward", "backward", "amp", "optimizer"}
    if which == "gpt_ddp4":
        expected |= {"reduce"}
        assert any(scopes.phase(line.strip(), None) == "collective"
                   for line in text.splitlines() if " all-reduce(" in line)
    assert expected <= {p for p, n in phases.items() if n}


def test_scopes_add_no_instruction(compiled_text):
    """Named scopes are metadata: with it stripped, a step traced under
    other scope names compiles to the same instruction lines."""
    def lines(text):
        return [re.sub(r", metadata=\{[^}]*\}", "", line)
                for line in text.splitlines() if " = " in line]

    real = jax.named_scope
    mine = set(profiling.TRAIN_STEP_SCOPES + profiling.MODEL_SCOPES)
    step, *args = STEPS["gpt"][0]()
    try:
        # the program's own scopes vanish; flax's module scopes stay
        jax.named_scope = lambda name: (
            contextlib.nullcontext() if name in mine else real(name))
        bare = step.lower(*args).compile().as_text()
    finally:
        jax.named_scope = real
    assert "amp_optimizer_step" not in bare
    assert lines(bare) == lines(compiled_text("gpt"))


# ---------------------------------------------------- the compile cache

_CACHE_PROBE = textwrap.dedent("""
    import sys
    import jax, jax.numpy as jnp
    scope, enable = sys.argv[1], sys.argv[2] == "enable"
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if enable:
        from apex_tpu.utils import compile_cache
        real = jax.default_backend
        jax.default_backend = lambda: "tpu"     # enable() is for the chip
        try:
            assert compile_cache.enable()
        finally:
            jax.default_backend = real

    def f(x):
        with jax.named_scope(scope):
            return jnp.tanh(x) * 2.0

    text = jax.jit(f).lower(jnp.ones((8, 8))).compile().as_text()
    jax.clear_caches()
    jax.jit(f).lower(jnp.ones((8, 8))).compile()    # from another line
    import os
    entries = sum(name.startswith("jit_f-") and name.endswith("-cache")
                  for name in os.listdir(
                      os.environ["JAX_COMPILATION_CACHE_DIR"]))
    import re
    names = set(re.findall(r'op_name="([^"]*)"', text))
    whole = all(n == "x" or n.startswith("jit(f)/scope_") for n in names)
    print("READS", "scope_old" in text, "scope_new" in text, entries,
          "paths whole" if whole else sorted(names))
""")


def _probe(cache_dir, scope: str, how: str) -> str:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir),
               PYTHONPATH=str(REPO))
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE, scope, how], env=env,
        capture_output=True, text=True, timeout=300, check=True).stdout
    return out.strip().splitlines()[-1]


def test_a_cache_filled_under_one_scope_name_does_not_answer_for_another(
        tmp_path):
    """JAX's persistent-cache key strips ``op_name`` unless told not to:
    a program that differs only in its scope names then gets the other
    tree's executable, names and all.  ``compile_cache.enable()`` keeps
    the metadata in the key."""
    plain, keyed = tmp_path / "plain", tmp_path / "keyed"
    whole = " paths whole"
    assert _probe(plain, "scope_old", "plain") == "READS True False 1" + whole
    # the fault, shown: the new name hits the old entry and reads the old
    assert _probe(plain, "scope_new", "plain") == "READS True False 1" + whole
    # with the names in the key each has its entry, and only one: lowering
    # the same function from another line of the program hits it; and every
    # operation still carries its whole scope path
    assert _probe(keyed, "scope_old", "enable") == "READS True False 1" + whole
    assert _probe(keyed, "scope_new", "enable") == "READS False True 2" + whole
