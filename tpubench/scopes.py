"""Which engine stage each device op of a trace belongs to.

The retrieve program gives every op of its three stages a named scope
(``warp.select``, ``warp.gather_score``, ``warp.reduce``), which the
compiler keeps in the op's ``op_name`` metadata. The profiler's XLA Ops
events do not carry that metadata: an event is named by its HLO
instruction's text. So the join goes through the optimized HLO of the
executables still loaded on the device after the window: each
instruction's text gives a key, and its ``op_name`` gives the stage.

- The key of an instruction is its name, result shape without layout,
  opcode and operand names. The trace prints operand shapes and layouts
  that the HLO text may not, so both sides are cut to that key. The bare
  name (``fusion.4``) is not enough: every module has one.
- An instruction's stage is the one ``warp.*`` scope in its ``op_name``;
  a fusion without one takes the one scope of the instructions it fuses,
  through fusions nested in it. Constants do not count there: the
  compiler merges equal constants of different stages into one.
- An op counts as unattributed when its key matches no instruction that
  has a stage, or matches several whose stages differ.

``python -m tpubench.scopes <file.xplane.pb> <hlo.txt>...`` prints the
split of a kept trace, stage by stage and the unattributed ops one by
one.
"""

from __future__ import annotations

import re
import sys

STAGES = ("warp.select", "warp.gather_score", "warp.reduce")
_SCOPE = re.compile(r"warp\.(?:select|gather_score|reduce)(?=[/)]|$)")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_MODULE = re.compile(r"^(?=HloModule )", re.MULTILINE)


def _close(text: str, i: int) -> int:
    """Index of the bracket that closes the one at ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        c = text[j]
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
            if depth == 0:
                return j
    return -1


def _parse(text: str):
    """(key, opcode, tail) of one HLO instruction's text, or None."""
    m = _INSTR.match(text)
    if m is None:
        return None
    name, rest = m.groups()
    if rest.startswith("("):  # tuple shape
        end = _close(rest, 0)
        if end < 0:
            return None
        shape, rest = rest[: end + 1], rest[end + 1:].lstrip()
    else:
        j = 0
        while j < len(rest) and rest[j] != " ":
            j = _close(rest, j) + 1 if rest[j] in "[{(" else j + 1
            if j <= 0:
                return None
        shape, rest = rest[:j], rest[j:].lstrip()
    p = rest.find("(")
    end = _close(rest, p) if p > 0 else -1
    if end < 0:
        return None
    opcode, operands = rest[:p], _OPERAND.findall(rest[p:end + 1])
    key = f"{name} = {_LAYOUT.sub('', shape)} {opcode}({','.join(operands)})"
    return key, opcode, rest[end + 1:]


def op_key(text: str) -> str | None:
    """The join key of a trace event's name (an HLO instruction's text)."""
    got = _parse(text)
    return None if got is None else got[0]


def _scopes(tail: str) -> set:
    m = _OP_NAME.search(tail)
    return set(_SCOPE.findall(m.group(1))) if m else set()


def _reach(calls, own: dict, nested: dict) -> set:
    """The scopes of the instructions in ``calls`` and, transitively, in
    the computations those call."""
    seen, todo, out = set(), list(calls), set()
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            out |= own.get(c, set())
            todo.extend(nested.get(c, ()))
    return out


def stage_map(hlo_texts) -> dict:
    """Key -> stage (``None`` where instructions of that key disagree),
    over every instruction that has a stage in the given HLO texts (one
    module each, or several one after another)."""
    out: dict = {}
    modules = [m for t in hlo_texts for m in _MODULE.split(t) if m.strip()]
    for text in modules:
        own: dict = {}  # computation -> scopes of its instructions
        rows = []  # (key, own scopes, called computations)
        called = []  # (computation, computations one instruction calls)
        comp = None
        for line in text.splitlines():
            m = _COMPUTATION.match(line)
            if m is not None and not line.startswith(" "):
                comp = m.group(1)
                continue
            got = _parse(line)
            if got is None:
                continue
            key, opcode, tail = got
            scopes = _scopes(tail)
            calls = _CALLS.findall(tail)
            if opcode != "constant":  # CSE shares constants across stages
                own.setdefault(comp, set()).update(scopes)
            called.append((comp, calls))
            rows.append((key, scopes, calls))
        nested = {}  # computation -> computations its instructions call
        for comp_, calls in called:
            nested.setdefault(comp_, set()).update(calls)
        for key, scopes, calls in rows:
            if not scopes:
                scopes = _reach(calls, own, nested)
            if len(scopes) != 1:
                continue
            stage = scopes.pop()
            out[key] = stage if out.get(key, stage) == stage else None
    return out


def split(op_seconds: dict, hlo_texts) -> tuple[dict, dict]:
    """(seconds per stage, seconds per unattributed op) of a trace's
    ``op_seconds`` (op text -> seconds)."""
    stages = stage_map(hlo_texts)
    per_stage = dict.fromkeys(STAGES, 0.0)
    rest: dict = {}
    for name, sec in op_seconds.items():
        stage = stages.get(op_key(name))
        if stage is None:
            rest[name] = rest.get(name, 0.0) + sec
        else:
            per_stage[stage] += sec
    return per_stage, rest


def live_hlo_texts() -> list[str]:
    """Optimized HLO, with metadata, of every executable loaded on the
    first device."""
    import jax

    return [
        m.to_string()
        for e in jax.devices()[0].client.live_executables()
        for m in e.hlo_modules()
    ]


_LAST: list = [None, None]  # [trace, split] of the last trace read


def stage_ms(run, stage: str) -> float | None:
    """Device time per dispatched batch of the ops under ``stage`` in a
    traced run, or None: no trace, or no op under that scope (a program
    without the scopes)."""
    tr = run.trace
    if tr is None:
        return None
    if _LAST[0] is not tr:
        _LAST[:] = [tr, split(tr.op_seconds, live_hlo_texts())]
    sec = _LAST[1][0][stage]
    return tr.per_batch_ms(sec / tr.n_chips) if sec > 0 else None


def main(argv) -> None:
    from tpubench import trace_reduce

    summary = trace_reduce.reduce(trace_reduce.load(argv[0]))
    texts = [open(p).read() for p in argv[1:]]
    per_stage, rest = split(summary.op_seconds, texts)
    n = len(summary.batches)
    total = sum(summary.op_seconds.values())
    for stage, sec in per_stage.items():
        print(f"{stage}: {sec / n * 1e3:.3f} ms per batch ({sec / total:.2%})")
    print(f"unattributed: {sum(rest.values()) / n * 1e3:.3f} ms per batch")
    for name, sec in sorted(rest.items(), key=lambda kv: -kv[1]):
        print(f"  {sec / n * 1e3:.4f} ms  {name[:160]}")


if __name__ == "__main__":
    main(sys.argv[1:])
