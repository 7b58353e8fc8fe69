"""Pallas TPU kernels for the compression hot path.

Every kernel wrapper takes ``interpret=None``: the kernels compile on a
TPU backend and run in the Pallas interpreter everywhere else (CPU
tests and rehearsals).  An explicit bool overrides the platform.
"""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Resolve a kernel's ``interpret`` argument: an explicit bool wins;
    ``None`` interprets exactly where the default backend is not a
    TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def count_pallas_calls(jaxpr) -> int:
    """Number of ``pallas_call`` launches in a jaxpr (a ``ClosedJaxpr``
    or a ``Jaxpr``), counted through every sub-jaxpr (pjit, scan, cond,
    shard_map bodies).  Duck-typed on ``.jaxpr`` / ``.eqns``, so it
    does not depend on where a JAX version keeps its jaxpr classes."""
    if hasattr(jaxpr, "jaxpr"):                    # ClosedJaxpr
        jaxpr = jaxpr.jaxpr

    def subs(v):
        if hasattr(v, "jaxpr") or hasattr(v, "eqns"):
            return [v]
        if isinstance(v, (list, tuple)):
            return [j for x in v for j in subs(x)]
        return []

    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for v in eqn.params.values():
            n += sum(count_pallas_calls(s) for s in subs(v))
    return n


def tpu_kernel_names(hlo_text: str) -> list:
    """Kernel function names of the compiled Pallas kernels
    (``tpu_custom_call``s) in a TPU executable's HLO text, in order.  A
    kernel that ran in the interpreter has no custom call, so its name
    is missing here."""
    import base64
    import re
    names = []
    for line in hlo_text.splitlines():
        if "tpu_custom_call" not in line:
            continue
        body = re.search(r'"body":"([A-Za-z0-9+/=]+)"', line)
        raw = base64.b64decode(body.group(1)) if body else b""
        found = re.findall(rb"_[A-Za-z0-9_]*_kernel\b", raw)
        names.append(found[0].decode() if found else "?")
    return names
