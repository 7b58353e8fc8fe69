"""The program's named scopes, matched in a reduced trace's name stacks.

The training step enters a ``jax.named_scope`` on each of its layers
(``repro.utils.scopes``); XLA keeps the scope path in every operation's
``op_name``, which ``lgcbench.trace`` attaches to each device operation
as its name stack.  A transformation wraps the first scope entered
under it: ``jit(step_fn)/fwd_bwd/vmap(transpose(jvp(xent)))/while/...``.
So a stack is split on ``/`` outside parentheses, each wrapper such as
``jvp(...)``, ``transpose(...)`` or ``vmap(...)`` is opened and its
inside split again, and a scope matches an equal path component.  An
operation the compiler made without a name stack counts with the scoped
operation that ran before it (``effective``); one the program names
outside every scope counts under none.

The names are the yardstick and are kept here, not imported: the
benchmark runs against programs that have none of them, where every
reader finds nothing and returns None.
"""
from __future__ import annotations

import re
import weakref
from functools import lru_cache
from typing import Callable, List, Tuple

FWD_BWD = "fwd_bwd"
XENT = "xent"
COMPRESS = "compress"
SELECT = "select"
AE = "ae"
EXCHANGE = "exchange"          # each plan op runs under exchange/<label>
OPTIMIZER = "optimizer"
NAMES = (FWD_BWD, XENT, COMPRESS, SELECT, AE, EXCHANGE, OPTIMIZER)
COMPRESSOR = (COMPRESS, SELECT, AE)    # the compressor's local work

WRAPPER = re.compile(r"^[A-Za-z_][\w.\-]*\((.*)\)$", re.S)


def _split(stack: str) -> List[str]:
    """``stack`` split on the ``/`` that lie outside parentheses."""
    parts, depth, cur = [], 0, []
    for ch in stack:
        if ch == "/" and depth == 0:
            parts.append("".join(cur))
            cur = []
            continue
        depth += (ch == "(") - (ch == ")")
        cur.append(ch)
    parts.append("".join(cur))
    return parts


@lru_cache(maxsize=None)
def components(stack: str) -> Tuple[str, ...]:
    """The path components of a name stack, transform wrappers opened:
    ``"jit(f)/vmap(jvp(a))/b"`` gives ``("f", "a", "b")``."""
    out: List[str] = []
    for part in _split(stack):
        m = WRAPPER.match(part)
        if m:
            out.extend(components(m.group(1)))
        elif part:
            out.append(part)
    return tuple(out)


def under(stack: str, *scopes: str) -> bool:
    """Whether the operation with this name stack runs under any of
    ``scopes``."""
    comps = components(stack)
    return any(s in comps for s in scopes)


def exchanged(stack: str) -> bool:
    return under(stack, EXCHANGE)


def scoped(stack: str) -> bool:
    """Whether a name stack lies under any of the program's scopes."""
    return not set(components(stack)).isdisjoint(NAMES)


def unnamed(stack: str) -> bool:
    """Whether a name stack names no place in the program: empty, or
    only an argument's name (``comp_state['v']``), as the compiler
    leaves it on copies and on operations a pass rewrote."""
    return len(_split(stack)) == 1 and not WRAPPER.match(stack)


def effective(trace):
    """The trace with every unnamed operation (``unnamed``) under the
    scope of the last synchronous operation before it on its device
    that has one, since it runs inside that region of the step.  On the
    chip these are copies, layout changes and ops a pass rewrote (the
    model's cumulative sums become ``reduce-window`` ops with no name
    stack).  An operation the program names outside every scope keeps
    its stack and counts under none.  Each operation keeps its own name
    stack after the effective one: ``[start, duration, op, effective
    stack, line, own stack]``."""
    if trace in _EFFECTIVE:
        return _EFFECTIVE[trace]
    from lgcbench.trace import Trace
    devices = {}
    for dev, ops in trace.devices.items():
        last, out = "", []
        for s, d, name, own, line in sorted(ops, key=lambda o: (o[0],
                                                                -o[1])):
            st = own
            if scoped(own):
                if line == "sync":
                    last = own
            elif unnamed(own):
                st = last
            out.append([s, d, name, st, line, own])
        devices[dev] = out
    eff = Trace({"window": list(trace.window), "devices": devices,
                 "host": trace.host})
    eff.pairs = eff.self_ns()
    _EFFECTIVE[trace] = eff
    return eff


# each trace's effective form and self times, made once for its readers
_EFFECTIVE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def scope_ns(trace, keep: Callable[[str], bool]) -> float:
    """Device self time in the window of the operations whose effective
    name stack ``keep`` accepts (synchronous operations, and collectives
    in flight on the async line, as ``Trace.class_ns`` counts them),
    averaged over the devices."""
    per = [sum(t for op, t in pairs if keep(op[3]))
           for pairs in effective(trace).pairs.values()]
    return sum(per) / len(per) if per else 0.0


def per_step_ms(ns: float, ctx) -> float | None:
    """Nanoseconds in the window as milliseconds per traced step, or
    None where nothing was found (a program without the scopes)."""
    return ns / ctx["steps"] / 1e6 if ns else None
