"""Stage III (mesh backend): mesh-level strategies -> shard_map + collectives.

This is our extension of the paper's strategy hierarchy to the multi-device
level (DESIGN.md section 2): ``map[mesh(ax)]`` distributes blocks over a named
mesh axis exactly as ``mapWorkgroup`` distributed blocks over OpenCL work
groups, and a ``reduce[mesh(ax)]`` over the distributed blocks becomes a
single ``lax.psum`` — the collective schedule in the lowered HLO is the one
the functional term dictates (strategy preservation at the collective level).

Canonical forms accepted (what the strategy rewrites produce):

  1. [Join] (Map_{mesh ax} f (Split c E))       -- sharded map
  2. Reduce_{mesh ax} (+|max) z (Map_{mesh ax} f (Split c E))  -- map+all-reduce

where E is built from input Vars with Zip (chunking commutes with Zip).
Argument Vars that do NOT flow through the Split (a scal's alpha, rmsnorm's
weight vector, matmul's B operand) are passed to every shard *replicated*
(``in_specs=PartitionSpec()``) — the mesh map shards the big operand and
broadcasts the small ones, exactly the data-parallel reading of the term.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as PS

from . import phrases as P
from .types import Arr, ExpT


class MeshFormError(TypeError):
    pass


def _peel_join(e: P.Phrase):
    if isinstance(e, P.Join):
        return e.e, True
    return e, False


def _chunk_expr(e: P.Phrase, c: int):
    """Rewrite E (Vars/Zip of Vars) to its local-chunk version, returning the
    rewritten expression plus the list of (var, chunked_var) pairs."""
    if isinstance(e, P.Var):
        d = P.exp_data(e)
        if not isinstance(d, Arr):
            raise MeshFormError("sharded input must be an array")
        local = P.Var(e.name, ExpT(Arr(c, d.elem)))
        return local, [(e, local)]
    if isinstance(e, P.Zip):
        a, pa = _chunk_expr(e.a, c)
        b, pb = _chunk_expr(e.b, c)
        return P.Zip(a, b), pa + pb
    raise MeshFormError(
        f"cannot shard through {type(e).__name__}; expected Var/Zip")


def compile_expr_shardmap(expr: P.Phrase, arg_vars: Sequence[P.Var],
                          mesh: Mesh, *, inner: str = "jnp",
                          check: bool = True,
                          interpret: Optional[bool] = None) -> Callable:
    """Compile a mesh-level functional strategy to a shard_map'd callable;
    ``interpret`` goes to the inner Pallas backend."""
    from . import stage3_jnp, stage3_pallas

    def compile_inner(e, vs):
        if inner == "pallas":
            return stage3_pallas.compile_expr_pallas(
                e, vs, check=check, interpret=interpret)
        return stage3_jnp.compile_expr(e, vs, check=check)

    names = [v.name for v in arg_vars]

    def extras_of(pairs):
        """Argument Vars not flowing through the Split: replicated inputs."""
        chunked = {v.name for v, _ in pairs}
        return [v for v in arg_vars if v.name not in chunked]

    # ---- form 2: distributed reduce --------------------------------------
    if isinstance(expr, P.Reduce) and expr.level.kind == "mesh":
        ax = expr.level.axis
        x = P.Var(P.fresh("x"), ExpT(P.exp_data(expr.init)))
        acc = P.Var(P.fresh("a"), ExpT(P.exp_data(expr.init)))
        body = expr.f(x, acc)
        if not (isinstance(body, P.BinOp) and body.op in ("add", "max")):
            raise MeshFormError("mesh reduce must combine with + or max")
        op = body.op
        inner_map = expr.e
        if not (isinstance(inner_map, P.Map)
                and inner_map.level.kind == "mesh"
                and inner_map.level.axis == ax):
            raise MeshFormError("mesh reduce must consume a mesh map")
        split = inner_map.e
        if not isinstance(split, P.Split):
            raise MeshFormError("mesh map must consume a split")
        nshards = mesh.shape[ax]
        d_in = P.exp_data(split)
        if d_in.n != nshards:
            raise MeshFormError(
                f"split yields {d_in.n} blocks but axis {ax!r} has {nshards}")
        local_e, pairs = _chunk_expr(split.e, split.n)
        extras = extras_of(pairs)
        blk = P.Var(P.fresh("blk"), ExpT(Arr(split.n, _elem(split))))
        per_block = inner_map.f(blk)
        local_vars = [lv for _, lv in pairs] + extras + [blk]
        local_fn = compile_inner(per_block, local_vars)

        def chunk_fn(*locs):
            from .interp import interp
            return interp(local_e, {lv.name: lo for (_, lv), lo
                                    in zip(pairs, locs)})

        in_specs = tuple(PS(ax) for _ in pairs) + tuple(PS() for _ in extras)
        out_specs = PS()

        def shard_fn(*args_in):
            locs, reps = args_in[:len(pairs)], args_in[len(pairs):]
            chunk = chunk_fn(*locs)
            part = local_fn(*(list(locs) + list(reps) + [chunk]))
            return jax.lax.psum(part, ax) if op == "add" \
                else jax.lax.pmax(part, ax)

        sm = jax.shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        order = [v.name for v, _ in pairs] + [v.name for v in extras]

        def fn(*args):
            env = dict(zip(names, args))
            return sm(*(env[n] for n in order))

        return fn

    # ---- form 1: sharded map ---------------------------------------------
    body_e, joined = _peel_join(expr)
    if isinstance(body_e, P.Map) and body_e.level.kind == "mesh":
        ax = body_e.level.axis
        split = body_e.e
        if not isinstance(split, P.Split):
            raise MeshFormError("mesh map must consume a split")
        nshards = mesh.shape[ax]
        d_in = P.exp_data(split)
        if d_in.n != nshards:
            raise MeshFormError(
                f"split yields {d_in.n} blocks but axis {ax!r} has {nshards}")
        local_e, pairs = _chunk_expr(split.e, split.n)
        extras = extras_of(pairs)
        blk = P.Var(P.fresh("blk"), ExpT(Arr(split.n, _elem(split))))
        per_block = body_e.f(blk)
        local_fn = compile_inner(
            per_block, [lv for _, lv in pairs] + extras + [blk])

        def chunk_fn(*locs):
            from .interp import interp
            return interp(local_e, {lv.name: lo for (_, lv), lo
                                    in zip(pairs, locs)})

        in_specs = tuple(PS(ax) for _ in pairs) + tuple(PS() for _ in extras)
        out_specs = PS(ax)

        def shard_fn(*args_in):
            locs, reps = args_in[:len(pairs)], args_in[len(pairs):]
            chunk = chunk_fn(*locs)
            out = local_fn(*(list(locs) + list(reps) + [chunk]))
            if not joined:
                out = jax.tree_util.tree_map(lambda l: l[None], out)
            return out

        sm = jax.shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        order = [v.name for v, _ in pairs] + [v.name for v in extras]

        def fn(*args):
            env = dict(zip(names, args))
            return sm(*(env[n] for n in order))

        return fn

    raise MeshFormError(
        "expression is not in a recognised mesh-level canonical form")


def _elem(split: P.Split):
    d = P.exp_data(split)
    assert isinstance(d, Arr) and isinstance(d.elem, Arr)
    return d.elem.elem


# self-register as a Stage III target (see repro.compiler.backends)
from repro.compiler.backends import Backend as _Backend  # noqa: E402
from repro.compiler.backends import register_backend as _register  # noqa: E402

_register(_Backend(
    name="shardmap", compile=compile_expr_shardmap,
    accepts=("mesh", "inner", "check", "interpret"), requires=("mesh",),
    description="mesh-level strategies -> shard_map + collectives (pass "
                "mesh=, optional inner='jnp'|'pallas')"),
    aliases=("dpia-shardmap",), overwrite=True)
