"""The program's own names for the parts of a train step, as the
benchmark reads them.

``apex_tpu`` opens a ``jax.named_scope`` around each phase of the step
it compiles and around the blocks of its models; the names travel as
``op_name`` metadata on the instructions of ``compiled.as_text()``.
This file holds the benchmark's copy of those names (the program's are
``apex_tpu/utils/profiling.py`` ``TRAIN_STEP_SCOPES`` and
``MODEL_SCOPES``; a tier-1 test fails when the two differ) and two pure
functions on strings: :func:`phase` and :func:`block` put one executed
instruction, by its name and its ``op_name``, into exactly one phase and
exactly one block.  The seconds are the device trace's
(``Run.instruction_seconds``: chip 0's self seconds by instruction); the
attribution is the program's.  A fusion is classified by the ``op_name``
on its own line, that of the instruction it was built around.

A program that opens none of these scopes (a commit before they were
added, or an executable cached from one) gives :func:`phase_seconds`
and :func:`block_seconds` nothing to read, and every metric built on
them is left out of the line.
"""

from __future__ import annotations

import collections
import re

from benchmark import trace

(AMP_CAST, AMP_REDUCE, AMP_UNSCALE, AMP_SCALER_UPDATE,
 AMP_OPTIMIZER_STEP) = TRAIN_STEP_SCOPES = (
    "amp_cast", "amp_reduce", "amp_unscale", "amp_scaler_update",
    "amp_optimizer_step")
MODEL_SCOPES = ("mlp", "lm_loss", "pretraining_loss")

PHASES = ("collective", "backward", "forward", "reduce", "amp", "optimizer",
          "unscoped")
BLOCKS = ("head_loss", "mlp", "attention", "norm", "embed", "other")

#: per family, the path segments (flax module names and the scopes of
#: ``MODEL_SCOPES``) that put an instruction into a block; the first
#: block with a segment on the path takes it
BLOCK_SEGMENTS = {
    "gpt": {
        "head_loss": ("lm_head", "lm_loss"),
        "mlp": ("mlp",),
        "attention": ("attention",),
        "norm": ("ln1", "ln2", "ln_f"),
        "embed": ("tok_emb",)},
    "bert": {
        "head_loss": ("mlm_transform", "mlm_ln", "mlm_decoder", "pooler",
                      "nsp", "pretraining_loss"),
        "mlp": ("mlp",),
        "attention": ("attention",),
        "norm": ("attention_ln", "ffn_ln", "emb_ln"),
        "embed": ("tok_emb", "pos_emb", "seg_emb")},
}

_SEGMENT = re.compile(r"[/()]+")


def segments(op_name: str) -> list:
    """``jit(step)/transpose(jvp(GPTModel))/block_3/mlp/ffn_in/dot_general``
    -> ``[jit, step, transpose, jvp, GPTModel, block_3, mlp, ...]``."""
    return [s for s in _SEGMENT.split(op_name) if s]


def phase(instruction: str, op_name: "str | None") -> str:
    """The one phase of :data:`PHASES` an executed instruction belongs
    to.  ``instruction`` is the event's whole text or the instruction's
    name.  A collective goes by what it is, wherever it was traced;
    autodiff's own stamps come next (recomputation under remat carries
    ``transpose(jvp(`` and counts as backward); then the program's
    scopes."""
    base = trace.kernel(instruction)
    if (trace.opcode(instruction) or base).startswith(trace.COLLECTIVES):
        return "collective"
    op_name = op_name or ""
    if "transpose(jvp(" in op_name or "vjp(" in op_name:
        return "backward"
    if "jvp(" in op_name:
        return "forward"
    on_path = set(op_name.split("/"))
    if AMP_REDUCE in on_path:
        return "reduce"
    if on_path & {AMP_CAST, AMP_UNSCALE, AMP_SCALER_UPDATE}:
        return "amp"
    if AMP_OPTIMIZER_STEP in on_path \
            or base in trace.OPTIMIZER_KERNELS or base.startswith("mt_"):
        return "optimizer"
    return "unscoped"


def block(op_name: "str | None", family: str) -> str:
    """The one block of :data:`BLOCKS` on the instruction's path, forward
    and backward alike, for a model of ``family`` (``cfg["family"]``)."""
    on_path = set(segments(op_name or ""))
    for name, marks in BLOCK_SEGMENTS[family].items():
        if on_path.intersection(marks):
            return name
    return "other"


def named(op_names: dict) -> bool:
    """Whether the compiled program carries the program's phase scopes at
    all (every amp step has an ``amp_optimizer_step``)."""
    return any(AMP_OPTIMIZER_STEP in name.split("/")
               for name in op_names.values())


def phase_seconds(run) -> dict:
    """Chip 0's self seconds by phase; empty where there is no trace or
    the program opened no scope."""
    sums: dict = collections.defaultdict(float)
    if run.instruction_seconds and named(run.op_names):
        for instr, seconds in run.instruction_seconds.items():
            sums[phase(instr, run.op_names.get(instr))] += seconds
    return dict(sums)


def block_seconds(run, skip_kernels: tuple = ()) -> dict:
    """Chip 0's self seconds by model block; instructions whose kernel
    name starts with one of ``skip_kernels`` are left out."""
    sums: dict = collections.defaultdict(float)
    if run.instruction_seconds and named(run.op_names):
        for instr, seconds in run.instruction_seconds.items():
            if not trace.kernel(instr).startswith(skip_kernels):
                sums[block(run.op_names.get(instr),
                           run.cfg["family"])] += seconds
    return dict(sums)


def share(run, seconds: "float | None") -> "float | None":
    """``seconds`` as a percentage of chip 0's busy time, or ``None``
    where there is nothing to read."""
    if not seconds or not run.busy_s0:
        return None
    return 100.0 * seconds / run.busy_s0
