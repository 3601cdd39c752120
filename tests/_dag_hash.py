"""A jaxpr's outputs hashed as a dataflow DAG: what each output is computed
FROM, not the order of the equations, the numbering of the variables, or
the source lines of the frames.  Two traces of one program hash equal; a
program that gains an output keeps the hashes of the outputs it had, so
:func:`dag_hash` can leave the new ones out (the check PRs 30 and 34 made
by hand on lowered texts)."""

import hashlib
import re

import jax
import numpy as np
from jax.extend import core as jcore

_NOISE = re.compile(r" at 0x[0-9a-f]+|\.py:\d+(?::\d+)?")
_SKIP = ("name_and_src_info", "debug_info", "debug", "metadata")


def _h(*parts) -> str:
    return hashlib.sha256("\x1f".join(map(str, parts)).encode()).hexdigest()


def _const(c) -> str:
    a = np.asarray(c)
    return _h("const", a.shape, a.dtype, hashlib.sha256(a.tobytes()).hexdigest())


def _canon(v):
    if isinstance(v, jcore.ClosedJaxpr):
        return _h("closed", outputs(v.jaxpr), *map(_const, v.consts))
    if isinstance(v, jcore.Jaxpr):
        return _h("jaxpr", outputs(v))
    if isinstance(v, (tuple, list)):
        return _h("seq", *map(_canon, v))
    if isinstance(v, dict):
        return _h("dict", *(f"{k}={_canon(x)}" for k, x in sorted(v.items())))
    if callable(v) and not isinstance(v, type):
        return _h("fn", getattr(v, "__name__", type(v).__name__))
    return _NOISE.sub("", repr(v))


def outputs(jaxpr) -> tuple:
    """One hash per output of ``jaxpr``, each of its whole ancestry."""
    seen = {}
    for i, var in enumerate((*jaxpr.constvars, *jaxpr.invars)):
        seen[var] = _h("in", i, var.aval)
    for eqn in jaxpr.eqns:
        ins = [_h("lit", v.val, v.aval) if isinstance(v, jcore.Literal)
               else seen[v] for v in eqn.invars]
        params = [f"{k}={_canon(v)}" for k, v in sorted(eqn.params.items())
                  if k not in _SKIP]
        node = _h(eqn.primitive.name, *params, "|", *ins)
        for j, var in enumerate(eqn.outvars):
            seen[var] = _h(node, j)
    return tuple(_h("lit", v.val, v.aval) if isinstance(v, jcore.Literal)
                 else seen[v] for v in jaxpr.outvars)


def dag_hash(fn, *args, drop=()) -> str:
    """The flat outputs of ``fn(*args)``, but those at the indices ``drop``,
    as one 16-digit hash of their dataflow DAGs."""
    closed = jax.make_jaxpr(fn)(*args)
    outs = [h for i, h in enumerate(outputs(closed.jaxpr)) if i not in drop]
    return _h(*outs, *map(_const, closed.consts))[:16]
