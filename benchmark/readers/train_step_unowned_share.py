"""How much of the step neither the program's names nor its data flow
reach: the share of chip 0's busy time in instructions that
``scopes.phase`` puts in ``unscoped`` and that stay there with the
owner ``benchmark/owners.py`` gives each nameless one (a copy takes the
``op_name`` of the fusion that reads it, a loop's operand that of the
loop).  ``train_step.unscoped_share`` reads the same instructions
before the owners; this one reads what is left of them.

``note:`` lines: ``train_step.unscoped_owners`` splits the unscoped
time by owner (phase and deepest scope, ms a step, the largest
first; the entries add up to ``train_step.unscoped_share``);
``train_step.unowned_top`` names the largest instructions no owner
reaches; ``train_step.unowned_share.cost`` says what building the
compiled text again and the walk cost, after the window."""

import time

from benchmark import compiled_step, owners, scopes

OWNERS_SHOWN, UNOWNED_SHOWN = 8, 5


def read(run) -> "float | None":
    if not scopes.phase_seconds(run) or not run.busy_s0:
        return None
    t = time.perf_counter()
    hlo_text = compiled_step.text(run)
    built = time.perf_counter() - t
    if hlo_text is None:
        run.notes["train_step.unowned_share.cost"] = (
            "the step built again carries other op_names than the one "
            "traced: no owner is read")
        return None
    program = owners.parse(hlo_text)
    found = owners.split(run, owners.inherit(program))
    run.notes["train_step.unowned_share.cost"] = (
        f"step compiled again in {built:.2f} s, {len(program.instructions)} "
        f"instructions walked in {time.perf_counter() - t - built:.2f} s")

    per_step = 1e3 / max(run.steps_traced, 1)

    def entry(what, seconds):
        return (f"{what} {seconds * per_step:.3f} ms "
                f"({100.0 * seconds / run.busy_s0:.3f}%)")

    ranked = sorted(found.by_owner.items(), key=lambda kv: -kv[1])
    shown = [entry(k, v) for k, v in ranked[:OWNERS_SHOWN]]
    if ranked[OWNERS_SHOWN:]:
        shown.append(entry("other owners",
                           sum(v for _, v in ranked[OWNERS_SHOWN:])))
    shown.append(entry("unowned", found.unowned))
    run.notes["train_step.unscoped_owners"] = " + ".join(shown) + \
        " = " + entry("unscoped a step", found.unscoped)
    left = sorted(found.unowned_by_instruction.items(),
                  key=lambda kv: -kv[1])[:UNOWNED_SHOWN]
    run.notes["train_step.unowned_top"] = "; ".join(
        f"{instr} {getattr(program.instructions.get(instr), 'opcode', '?')} "
        f"{seconds * per_step:.3f} ms" for instr, seconds in left) or "none"
    return 100.0 * found.unowned / run.busy_s0
