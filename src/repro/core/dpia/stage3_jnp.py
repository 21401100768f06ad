"""Stage III (jnp backend): purely imperative DPIA -> executable JAX.

This is the analogue of the paper's Fig. 6 translation to parallel pseudo-C,
re-targeted at JAX: commands become store transformers (the store is a dict of
buffer pytrees), acceptors resolve to (root, index-path) l-values exactly as
in Fig. 6b, and expressions are evaluated by the functional interpreter
(Fig. 6c).  ``for``/``parfor`` become ``lax.fori_loop`` (the reference
execution order; the Pallas backend gives parfor its parallel reading).

The index-path discipline mirrors the paper: acceptor combinators transform an
accumulated path of indices / ``fst|snd`` projections / dynamic slices until
an identifier is reached, at which point the path is applied to the buffer.
Because buffers are struct-of-arrays pytrees, ``splitAcc``/``joinAcc``/
``asScalarAcc`` are reshape re-views rather than flat-index arithmetic.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Set, Tuple

import jax
import jax.numpy as jnp

from . import phrases as P
from . import stage2
from .interp import interp
from .types import AccT, Arr, ExpT, Idx, Pair, VarT, Vec, zero_value

Store = Dict[str, object]

FST, SND = "fst", "snd"


# ---------------------------------------------------------------------------
# l-value writes: set_path + acceptor resolution (Fig. 6b)
# ---------------------------------------------------------------------------

def _cast_like(buf, value):
    return jax.tree_util.tree_map(
        lambda b, v: jnp.asarray(v, b.dtype).reshape(b.shape), buf, value)


def set_path(buf, path: Sequence, value):  # noqa: C901
    """Functionally update ``buf`` at ``path`` with ``value``.

    Path components: integer (possibly traced) indices, ('ds', start, size)
    dynamic slices along the leading axis, and 'fst'/'snd' pair projections.
    """
    if not path:
        return _cast_like(buf, value)
    if isinstance(buf, tuple):
        for k, comp in enumerate(path):
            if comp in (FST, SND):
                b = 0 if comp == FST else 1
                rest = list(path[:k]) + list(path[k + 1:])
                parts = list(buf)
                parts[b] = set_path(buf[b], rest, value)
                return tuple(parts)
        # whole-pair write: value must be a matching tuple
        return tuple(set_path(bi, path, vi) for bi, vi in zip(buf, value))
    comp, rest = path[0], path[1:]
    if isinstance(comp, tuple) and comp[0] == "ds":
        _, start, size = comp
        sub = jax.lax.dynamic_slice_in_dim(buf, start, size, axis=0)
        sub = set_path(sub, rest, value)
        return jax.lax.dynamic_update_slice_in_dim(buf, sub, start, axis=0)
    if comp in (FST, SND):
        raise TypeError("pair projection applied to a non-pair buffer")
    # integer index
    if not rest:
        return buf.at[comp].set(jnp.asarray(value, buf.dtype))
    sub = set_path(buf[comp], rest, value)
    return buf.at[comp].set(sub)


def _reshape_leading(value, old: Tuple[int, ...], new: Tuple[int, ...]):
    """Re-view the leading axes of every leaf of ``value``."""
    def fix(l):
        return l.reshape(tuple(new) + l.shape[len(old):])
    return jax.tree_util.tree_map(fix, value)


def fold_acc(a: P.Phrase, idxs: List, value, eval_i, leaf):  # noqa: C901
    """Resolve an acceptor phrase down to its root, threading the index path
    (Fig. 6b discipline).  ``eval_i`` evaluates index expressions; ``leaf`` is
    called as ``leaf(root_phrase, idxs, value)`` at a Var / AccPart root.
    Shared by the jnp and Pallas backends."""
    if isinstance(a, P.Var):
        assert isinstance(a.t, AccT), f"write through non-acceptor {a.t}"
        return leaf(a, idxs, value)
    if isinstance(a, P.AccPart):
        v = a.v
        if isinstance(v, P.VView):
            return fold_acc(v.acc, idxs, value, eval_i, leaf)
        assert isinstance(v, P.Var) and isinstance(v.t, VarT)
        return leaf(a, idxs, value)
    if isinstance(a, P.IdxAcc):
        i = eval_i(a.i)
        return fold_acc(a.a, [i] + idxs, value, eval_i, leaf)
    if isinstance(a, P.SplitAcc):
        # self: acc[(m*n).d]; inner: acc[m.n.d]
        n = a.n
        if idxs:
            i, rest = idxs[0], idxs[1:]
            if isinstance(i, tuple) and i[0] == "ds":
                _, s0, sz = i
                if isinstance(s0, int) and isinstance(sz, int) \
                        and s0 % n == 0 and sz % n == 0 and not rest:
                    return fold_acc(
                        a.a, [("ds", s0 // n, sz // n)],
                        _reshape_leading(value, (sz,), (sz // n, n)),
                        eval_i, leaf)
                raise TypeError(
                    "splitAcc: unaligned slice writes across chunks")
            return fold_acc(a.a, [i // n, i % n] + rest, value, eval_i, leaf)
        inner_d = P.acc_data(a.a)
        assert isinstance(inner_d, Arr)
        m = inner_d.n
        return fold_acc(a.a, [], _reshape_leading(value, (m * n,), (m, n)),
                        eval_i, leaf)
    if isinstance(a, P.JoinAcc):
        # self: acc[k.m.d]; inner: acc[(k*m).d]
        m = a.m
        if len(idxs) >= 2:
            i, j, rest = idxs[0], idxs[1], idxs[2:]
            if isinstance(i, tuple):
                raise TypeError("joinAcc: mixed slice/index writes unsupported")
            if isinstance(j, tuple):  # a slice within chunk i
                return fold_acc(a.a, [("ds", i * m + j[1], j[2])] + rest,
                                value, eval_i, leaf)
            return fold_acc(a.a, [i * m + j] + rest, value, eval_i, leaf)
        if len(idxs) == 1:
            i = idxs[0]
            if isinstance(i, tuple) and i[0] == "ds":
                _, s0, sz = i
                return fold_acc(
                    a.a, [("ds", s0 * m, sz * m)],
                    _reshape_leading(value, (sz, m), (sz * m,)),
                    eval_i, leaf)
            return fold_acc(a.a, [("ds", i * m, m)], value, eval_i, leaf)
        d = P.acc_data(a)
        assert isinstance(d, Arr)
        k = d.n
        return fold_acc(a.a, [], _reshape_leading(value, (k, m), (k * m,)),
                        eval_i, leaf)
    if isinstance(a, P.TransposeAcc):
        # self: acc[n.m.d]; inner: acc[m.n.d] — swap leading index pair.
        if len(idxs) >= 2:
            i, j, rest = idxs[0], idxs[1], idxs[2:]
            return fold_acc(a.a, [j, i] + rest, value, eval_i, leaf)
        if len(idxs) == 1:
            raise TypeError("transposeAcc: single-index (column) writes "
                            "unsupported; write whole or per-element")
        value_t = jax.tree_util.tree_map(lambda l: jnp.swapaxes(l, 0, 1), value)
        return fold_acc(a.a, [], value_t, eval_i, leaf)
    if isinstance(a, P.PairAcc1):
        return fold_acc(a.a, [FST] + idxs, value, eval_i, leaf)
    if isinstance(a, P.PairAcc2):
        return fold_acc(a.a, [SND] + idxs, value, eval_i, leaf)
    if isinstance(a, P.ZipAcc1):
        return fold_acc(a.a, [FST] + idxs, value, eval_i, leaf)
    if isinstance(a, P.ZipAcc2):
        return fold_acc(a.a, [SND] + idxs, value, eval_i, leaf)
    if isinstance(a, P.AsScalarAcc):
        # self: acc[(m*w).num]; inner: acc[m.num<w>]
        inner_d = P.acc_data(a.a)
        assert isinstance(inner_d, Arr) and isinstance(inner_d.elem, Vec)
        m, w = inner_d.n, inner_d.elem.n
        if idxs:
            i, rest = idxs[0], idxs[1:]
            if isinstance(i, tuple) and i[0] == "ds":
                _, s0, sz = i
                if isinstance(s0, int) and isinstance(sz, int) \
                        and s0 % w == 0 and sz % w == 0 and not rest:
                    return fold_acc(
                        a.a, [("ds", s0 // w, sz // w)],
                        _reshape_leading(value, (sz,), (sz // w, w)),
                        eval_i, leaf)
                raise TypeError("asScalarAcc: unaligned slice write")
            return fold_acc(a.a, [i // w, i % w] + rest, value, eval_i, leaf)
        return fold_acc(a.a, [], _reshape_leading(value, (m * w,), (m, w)),
                        eval_i, leaf)
    if isinstance(a, P.AsVectorAcc):
        # self: acc[m.num<w>]; inner: acc[(m*w).num]
        w = a.w
        if len(idxs) >= 2:
            i, j, rest = idxs[0], idxs[1], idxs[2:]
            if isinstance(i, tuple) or isinstance(j, tuple):
                raise TypeError("asVectorAcc: mixed slice/index unsupported")
            return fold_acc(a.a, [i * w + j] + rest, value, eval_i, leaf)
        if len(idxs) == 1:
            i = idxs[0]
            if isinstance(i, tuple) and i[0] == "ds":
                _, s0, sz = i
                return fold_acc(
                    a.a, [("ds", s0 * w, sz * w)],
                    _reshape_leading(value, (sz, w), (sz * w,)),
                    eval_i, leaf)
            return fold_acc(a.a, [("ds", i * w, w)], value, eval_i, leaf)
        d = P.acc_data(a)
        assert isinstance(d, Arr)
        m = d.n
        return fold_acc(a.a, [], _reshape_leading(value, (m, w), (m * w,)),
                        eval_i, leaf)
    raise TypeError(f"fold_acc: unhandled acceptor {type(a).__name__}")


def write_acc(a: P.Phrase, idxs: List, value, env, store: Store) -> Store:
    """Resolve an acceptor phrase and write ``value`` into the store."""
    def leaf(root, path, val):
        name = root.name if isinstance(root, P.Var) else root.v.name
        new_store = dict(store)
        new_store[name] = set_path(new_store[name], path, val)
        return new_store

    return fold_acc(a, idxs, value,
                    lambda i: interp(i, env, store), leaf)


def acc_root(a: P.Phrase) -> str:
    """Root identifier of an acceptor chain."""
    if isinstance(a, P.Var):
        return a.name
    if isinstance(a, P.AccPart):
        if isinstance(a.v, P.VView):
            return acc_root(a.v.acc)
        assert isinstance(a.v, P.Var)
        return a.v.name
    inner = getattr(a, "a", None)
    if isinstance(inner, P.Phrase):
        return acc_root(inner)
    raise TypeError(f"acc_root: {type(a).__name__}")


# ---------------------------------------------------------------------------
# Static analysis: which store buffers does a command write?
# ---------------------------------------------------------------------------

def written_roots(p: P.Phrase, bound: Set[str] = frozenset()) -> Set[str]:  # noqa: C901
    out: Set[str] = set()

    def go(q: P.Phrase, bnd: Set[str]) -> None:
        if isinstance(q, P.Assign):
            r = acc_root(q.a)
            if r not in bnd:
                out.add(r)
            return
        if isinstance(q, P.SeqC):
            go(q.c1, bnd)
            go(q.c2, bnd)
            return
        if isinstance(q, P.Skip):
            return
        if isinstance(q, P.New):
            v = P.Var(P.fresh("v"), VarT(q.d))
            go(q.f(v), bnd | {v.name})
            return
        if isinstance(q, P.For):
            i = P.Var(P.fresh("i"), ExpT(Idx(q.n)))
            go(q.f(i), bnd)
            return
        if isinstance(q, P.ParFor):
            r = acc_root(q.a)
            if r not in bnd:
                out.add(r)
            i = P.Var(P.fresh("i"), ExpT(Idx(q.n)))
            o = P.Var(P.fresh("o"), AccT(q.d))
            go(q.f(i, o), bnd | {o.name})
            return
        if isinstance(q, (P.MapI, P.ReduceI)):
            go(stage2.expand(q), bnd)
            return
        raise TypeError(f"written_roots: not a command {type(q).__name__}")

    go(p, set(bound))
    return out


# ---------------------------------------------------------------------------
# Command execution (store-passing)
# ---------------------------------------------------------------------------

_UNROLL_DEFAULT = 8


def exec_comm(p: P.Phrase, env: Dict, store: Store) -> Store:  # noqa: C901
    if isinstance(p, P.Skip):
        return store
    if isinstance(p, P.SeqC):
        return exec_comm(p.c2, env, exec_comm(p.c1, env, store))
    if isinstance(p, P.Assign):
        value = interp(p.e, env, store)
        return write_acc(p.a, [], value, env, store)
    if isinstance(p, P.New):
        v = P.Var(P.fresh("buf"), VarT(p.d))
        store2 = dict(store)
        store2[v.name] = zero_value(p.d)
        store3 = exec_comm(p.f(v), env, store2)
        store3 = dict(store3)
        del store3[v.name]
        return store3
    if isinstance(p, P.For):
        return _run_loop(p.n, lambda i: p.f(i), env, store,
                         unroll=p.unroll or p.n <= _UNROLL_DEFAULT)
    if isinstance(p, P.ParFor):
        # Reference (sequential) execution order; parallel semantics is the
        # Pallas/shard_map backend's job.  Race freedom was checked upstream,
        # so orders agree.
        return _run_loop(p.n, lambda i: p.f(i, P.IdxAcc(p.a, i)), env, store,
                         unroll=p.n <= _UNROLL_DEFAULT)
    if isinstance(p, (P.MapI, P.ReduceI)):
        return exec_comm(stage2.expand(p), env, store)
    raise TypeError(f"exec_comm: not a command: {type(p).__name__}")


def _run_loop(n: int, mk_body, env: Dict, store: Store, unroll: bool) -> Store:
    i_probe = P.Var(P.fresh("i"), ExpT(Idx(n)))
    body_phrase = mk_body(i_probe)
    roots = sorted(r for r in written_roots(body_phrase) if r in store)

    if unroll:
        for k in range(n):
            env2 = {**env, i_probe.name: jnp.asarray(k, "int32")}
            store = exec_comm(body_phrase, env2, store)
        return store

    carry0 = tuple(store[r] for r in roots)

    def body(k, carry):
        st = dict(store)
        st.update(dict(zip(roots, carry)))
        env2 = {**env, i_probe.name: k}
        st2 = exec_comm(body_phrase, env2, st)
        return tuple(st2[r] for r in roots)

    final = jax.lax.fori_loop(0, n, body, carry0)
    out = dict(store)
    out.update(dict(zip(roots, final)))
    return out


# ---------------------------------------------------------------------------
# Whole-pipeline driver
# ---------------------------------------------------------------------------

def compile_expr(expr: P.Phrase, arg_vars, *, check: bool = True,
                 lowered=None):
    """Functional expression -> python callable via Stages I-III (jnp).

    Returns ``fn(*arrays) -> value`` suitable for jax.jit.  ``lowered``
    optionally supplies an already-translated ``(command, out_var)`` pair
    (the staged repro.compiler path) so Stage I/II is not redone here.
    """
    from . import check as chk
    from . import stage1

    if lowered is not None:
        cmd, out = lowered
        d = out.t.d
    else:
        d = P.exp_data(expr)
        out = P.Var("out#", AccT(d))
        cmd = stage2.expand(stage1.translate(expr, out))
    if check:
        P.type_of(cmd)
        chk.check_race_free(cmd)
    names = [v.name for v in arg_vars]
    out_name = out.name

    def fn(*args):
        env = dict(zip(names, args))
        store: Store = {out_name: zero_value(d)}
        store = exec_comm(cmd, env, store)
        return store[out_name]

    return fn


# self-register as a Stage III target (see repro.compiler.backends)
from repro.compiler.backends import Backend as _Backend  # noqa: E402
from repro.compiler.backends import register_backend as _register  # noqa: E402

_register(_Backend(
    name="jnp", compile=compile_expr, accepts=("check", "lowered"),
    description="imperative DPIA -> executable JAX (lax.fori_loop reference "
                "order)"),
    aliases=("dpia-jnp",), overwrite=True)
