"""Functional reference semantics of DPIA expressions (paper section 5.2).

``interp(E, env)`` is the denotation [[E]] used as the oracle for translation
correctness (Theorem 5.1 as an executable property).  Values are pytrees:

  * ``Arr(n, d)``   -> leading axis of size n on every leaf
  * ``Pair(a, b)``  -> python 2-tuple (struct-of-arrays)
  * ``Vec(w, dt)``  -> trailing lane axis of size w
  * ``Num/Idx``     -> scalar jnp arrays

The interpreter is trace-compatible: it can run under jit/vmap, which is how
``map`` is given its parallel semantics here (vmap = the mathematical reading).

A leaf may also be a :class:`Lazy` value that is read only when a
computation consumes it.  The Pallas backend binds kernel inputs to lazy
views of their refs, so the layout combinators (split, join, idx,
transpose, asVector, asScalar) compose into index arithmetic on the ref and
only the slice a computation needs is loaded: the paper's compilation of
views to indices (section 6).
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from . import phrases as P
from .types import Arr, ExpT, Num, Pair, dtype_of, shape_of

Env = Dict[str, object]


class Lazy:
    """A value read on demand.  ``load()`` gives the jax value; subclasses
    that are array views also implement the layout combinators on their
    leading axes (``split``, ``join``, ``index``, ``transpose``), each of
    which returns another ``Lazy`` or, where the view cannot express the
    result, the loaded jax value."""

    def load(self):
        raise NotImplementedError


def force(v):
    """``v`` with every :class:`Lazy` leaf loaded."""
    return jax.tree_util.tree_map(
        lambda l: l.load() if isinstance(l, Lazy) else l, v)


def _split(l, n: int):
    if isinstance(l, Lazy):
        return l.split(n)
    return l.reshape((l.shape[0] // n, n) + l.shape[1:])


def _join(l):
    if isinstance(l, Lazy):
        return l.join()
    return l.reshape((l.shape[0] * l.shape[1],) + l.shape[2:])


def _index(l, i):
    if isinstance(l, Lazy):
        return l.index(i)
    return l[force(i)]


def _transpose(l):
    if isinstance(l, Lazy):
        return l.transpose()
    return jnp.swapaxes(l, 0, 1)

_UNOPS: Dict[str, Callable] = {
    "neg": lambda x: -x,
    "exp": jnp.exp,
    "log": jnp.log,
    "abs": jnp.abs,
    "rsqrt": jax.lax.rsqrt,
    "tanh": jnp.tanh,
    "sigmoid": jax.nn.sigmoid,
}

_BINOPS: Dict[str, Callable] = {
    "add": jnp.add,
    "sub": jnp.subtract,
    "mul": jnp.multiply,
    "div": jnp.divide,
    "max": jnp.maximum,
    "min": jnp.minimum,
}


def interp(p: P.Phrase, env: Env, store: Optional[Env] = None):  # noqa: C901
    """Denotation of a functional expression phrase.

    ``store`` optionally resolves ``ExpPart`` reads of imperative variables —
    used when the same evaluator serves as the expression (r-value) evaluator
    of the imperative backend (paper Fig. 6c).
    """
    rec = lambda q: interp(q, env, store)  # noqa: E731
    val = lambda q: force(interp(q, env, store))  # noqa: E731

    if isinstance(p, P.Var):
        try:
            return env[p.name]
        except KeyError:
            raise NameError(f"unbound DPIA variable {p.name!r}") from None
    if isinstance(p, P.ExpPart):
        v = p.v
        if isinstance(v, P.VView):
            return rec(v.exp)
        assert isinstance(v, P.Var), "ExpPart of non-variable"
        src = store if store is not None and v.name in store else env
        return src[v.name]
    if isinstance(p, P.Lit):
        shp = shape_of(p.d)
        if shp:
            return jnp.full(shp, p.value, dtype=dtype_of(p.d))
        return jnp.asarray(p.value, dtype=dtype_of(p.d))
    if isinstance(p, P.UnOp):
        return _UNOPS[p.op](val(p.e))
    if isinstance(p, P.BinOp):
        return _BINOPS[p.op](val(p.a), val(p.b))
    if isinstance(p, P.Map):
        xs = val(p.e)
        d = P.exp_data(p.e)
        assert isinstance(d, Arr)
        x = P.Var(P.fresh("x"), ExpT(d.elem))
        body = p.f(x)

        def apply_elem(xv):
            return interp(body, {**env, x.name: xv}, store)

        return jax.vmap(apply_elem)(xs)
    if isinstance(p, P.Reduce):
        xs = val(p.e)
        init = val(p.init)
        d = P.exp_data(p.e)
        assert isinstance(d, Arr)
        x = P.Var(P.fresh("x"), ExpT(d.elem))
        acc = P.Var(P.fresh("acc"), P.type_of(p.init))
        body = p.f(x, acc)

        def step(carry, xv):
            out = interp(body, {**env, x.name: xv, acc.name: carry}, store)
            return out, None

        final, _ = jax.lax.scan(step, init, xs)
        return final
    if isinstance(p, P.Zip):
        return (rec(p.a), rec(p.b))
    if isinstance(p, P.Split):
        v = rec(p.e)
        return jax.tree_util.tree_map(lambda l: _split(l, p.n), v)
    if isinstance(p, P.Join):
        return jax.tree_util.tree_map(_join, rec(p.e))
    if isinstance(p, P.PairE):
        return (rec(p.a), rec(p.b))
    if isinstance(p, P.Fst):
        return rec(p.e)[0]
    if isinstance(p, P.Snd):
        return rec(p.e)[1]
    if isinstance(p, P.IdxE):
        v = rec(p.e)
        i = rec(p.i)
        return jax.tree_util.tree_map(lambda l: _index(l, i), v)
    if isinstance(p, P.AsVector):
        return _split(rec(p.e), p.w)
    if isinstance(p, P.AsScalar):
        return _join(rec(p.e))
    if isinstance(p, P.Transpose):
        return jax.tree_util.tree_map(_transpose, rec(p.e))
    if isinstance(p, P.DotBlock):
        a, b = val(p.a), val(p.b)
        return jnp.matmul(a, b, preferred_element_type=p.acc_dtype)
    if isinstance(p, P.FullReduce):
        v = val(p.e)
        return jnp.sum(v) if p.op == "add" else jnp.max(v)
    if isinstance(p, P.ToMem):
        return rec(p.e)
    raise TypeError(f"interp: not a functional expression: {type(p).__name__}")


def interp_fn(expr: P.Phrase, arg_vars):
    """Close an expression over named argument Vars -> python callable."""
    names = [v.name for v in arg_vars]

    def fn(*vals):
        return interp(expr, dict(zip(names, vals)))

    return fn
