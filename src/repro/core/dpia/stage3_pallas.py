"""Stage III (Pallas backend): grid-level DPIA -> pl.pallas_call kernels.

The TPU re-basing of the paper's OpenCL code generator (section 6):

  * ``parfor[grid(k)]`` nests  ->  Pallas grid dimensions (the paper's
    parforWorkgroup/parforLocal -> get_group_id/get_local_id loops);
  * views -> indices: kernel inputs are bound to lazy views of their refs
    (:class:`_RefView`), so split/join/idx/transpose/asVector/asScalar
    compose into affine index arithmetic over the grid and loop indices
    (:class:`Aff`) and a computation loads only the slice it consumes, as
    ``ref[pl.ds(start, size)]``;
  * the grid-level split of the strategy -> ``BlockSpec``s: a planning trace
    of the kernel body records every access, and an operand whose accesses
    all fall in a grid-indexed block of it is handed to the kernel block by
    block (:meth:`_Buf.place`); a block too large for VMEM stays in HBM and
    each read of it is a DMA into VMEM scratch;
  * the SCIR acceptor discipline -> disjoint explicit stores into the output
    ref, with index paths computed exactly as in Fig. 6b;
  * ``new[vmem]``   -> kernel scratch (the paper's hoisted local allocations);
  * ``new[reg]``    -> loop-carried SSA values (TPU: VREG accumulators);
  * ``for``         -> in-kernel ``lax.fori_loop``;
  * non-grid top-level commands -> host-side execution (the paper's host code
    between kernel launches), with HBM temporaries as jnp buffers.

Kernels are emitted for the TPU (Mosaic's tiling rules decide which blocks
are legal) and run on the CPU with ``interpret=True``; both modes run the
same placement.
"""
from __future__ import annotations

import math
import operator
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import phrases as P
from . import stage1, stage2
from .interp import Lazy, force, interp
from .stage3_jnp import (FST, SND, Store, acc_root, exec_comm, fold_acc,
                         set_path, written_roots)
from .types import (AccT, Arr, DataType, ExpT, Idx, Num, Pair, VarT, Vec,
                    dtype_of, zero_value)

# TPU v5e has 128 MiB of VMEM per core; Mosaic's default scoped limit is
# 16 MiB, too small for a (128, 9728) float32 output block double-buffered.
VMEM_LIMIT_BYTES = 100 * 2 ** 20
# an input whose double-buffered block exceeds this stays in HBM (DMA reads)
VMEM_OPERAND_BYTES = 16 * 2 ** 20
# arrays read or written only element by element go to SMEM up to this size
SMEM_BYTES = 64 * 2 ** 10


# ---------------------------------------------------------------------------
# helpers: data types <-> ref pytrees
# ---------------------------------------------------------------------------

def _leaf_shapes(d: DataType):
    """Pytree of (shape, dtype) mirroring the buffer layout of ``d``."""
    if isinstance(d, (Num, Idx)):
        return ((), dtype_of(d))
    if isinstance(d, Vec):
        return ((d.n,), d.dtype)
    if isinstance(d, Arr):
        inner = _leaf_shapes(d.elem)
        return jax.tree_util.tree_map(
            lambda sd: ((d.n,) + sd[0], sd[1]), inner,
            is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
            and isinstance(x[0], tuple))
    if isinstance(d, Pair):
        return (_leaf_shapes(d.fst), _leaf_shapes(d.snd))
    raise TypeError(d)


def _flat_leaf_shapes(d: DataType) -> List[Tuple[Tuple[int, ...], str]]:
    out: List[Tuple[Tuple[int, ...], str]] = []

    def go(n):
        if isinstance(n, tuple) and len(n) == 2 and isinstance(n[0], tuple) \
                and all(isinstance(x, int) for x in n[0]):
            out.append(n)
        else:
            for c in n:
                go(c)

    go(_leaf_shapes(d))
    return out


def _strip_arr(d: DataType) -> DataType:
    while isinstance(d, Arr):
        d = d.elem
    return d


def _build_ref_tree(d: DataType, leaves_iter):
    if isinstance(_strip_arr(d), Pair):
        core = _strip_arr(d)
        return (_build_ref_tree(core.fst, leaves_iter),
                _build_ref_tree(core.snd, leaves_iter))
    return next(leaves_iter)


# ---------------------------------------------------------------------------
# affine indices
# ---------------------------------------------------------------------------

class _Sym:
    """A kernel index: a grid program id (``grid`` = its axis) or a loop
    counter, with its traced value and extent ``n`` (None: unknown range)."""
    __slots__ = ("value", "n", "grid")

    def __init__(self, value, n: Optional[int] = None,
                 grid: Optional[int] = None):
        self.value, self.n, self.grid = value, n, grid


class Aff(Lazy):
    """An index ``const + sum(coeff * sym)`` over the kernel's grid and loop
    indices.  Kept symbolic so that block origins cancel exactly and starts
    are static where they can be; anything non-affine becomes an opaque
    symbol.  ``load()`` gives the traced int32 value."""
    __slots__ = ("const", "terms")

    def __init__(self, const: int = 0, terms: Optional[Dict] = None):
        self.const = int(const)
        self.terms = {s: c for s, c in (terms or {}).items() if c}

    @staticmethod
    def of(x) -> "Aff":
        if isinstance(x, Aff):
            return x
        try:  # python ints and concrete integer arrays are static
            return Aff(operator.index(x))
        except TypeError:
            return Aff(0, {_Sym(jnp.asarray(x, jnp.int32)): 1})

    @staticmethod
    def sym(s: _Sym) -> "Aff":
        return Aff(0, {s: 1})

    def _scaled(self, k: int) -> "Aff":
        return Aff(self.const * k, {s: c * k for s, c in self.terms.items()})

    def __add__(self, o) -> "Aff":
        o = Aff.of(o)
        terms = dict(self.terms)
        for s, c in o.terms.items():
            terms[s] = terms.get(s, 0) + c
        return Aff(self.const + o.const, terms)

    __radd__ = __add__

    def __sub__(self, o) -> "Aff":
        return self + Aff.of(o)._scaled(-1)

    def __mul__(self, o) -> "Aff":
        o = Aff.of(o)
        if not o.terms:
            return self._scaled(o.const)
        if not self.terms:
            return o._scaled(self.const)
        return Aff.of(self.load() * o.load())

    __rmul__ = __mul__

    def __floordiv__(self, n: int) -> "Aff":
        if all(c % n == 0 for c in self.terms.values()):
            return Aff(self.const // n,
                       {s: c // n for s, c in self.terms.items()})
        return Aff.of(self.load() // n)

    def __mod__(self, n: int) -> "Aff":
        if all(c % n == 0 for c in self.terms.values()):
            return Aff(self.const % n)
        return Aff.of(self.load() % n)

    def load(self):
        v = jnp.int32(self.const)
        for s, c in self.terms.items():
            v = v + c * s.value
        return v

    def bounds(self) -> Optional[Tuple[int, int]]:
        """(min, max) over the symbols' ranges; None if one is unknown."""
        lo = hi = self.const
        for s, c in self.terms.items():
            if s.n is None:
                return None
            lo += min(0, c * (s.n - 1))
            hi += max(0, c * (s.n - 1))
        return lo, hi

    def start(self):
        """A ref index: a python int when static, else the traced value
        marked with the largest power of two dividing every term."""
        if not self.terms:
            return self.const
        g = 0
        for c in list(self.terms.values()) + [self.const]:
            g = math.gcd(g, c)
        g = g & -g if g else 1
        v = self.load()
        return pl.multiple_of(v, g) if g > 1 else v


def _concrete(path: Sequence) -> list:
    """An acceptor path with its symbolic indices made traced values."""
    out = []
    for c in path:
        if isinstance(c, Aff):
            c = c.load()
        elif isinstance(c, tuple) and c[0] == "ds":
            c = ("ds", c[1].load() if isinstance(c[1], Aff) else c[1], c[2])
        out.append(c)
    return out


# ---------------------------------------------------------------------------
# buffers and their views
# ---------------------------------------------------------------------------

def _sublanes(dtype) -> int:
    return 8 * 4 // max(1, jnp.dtype(dtype).itemsize)


class _Buf:
    """One array leaf a kernel reads or writes, and where it lives.

    ``mem`` is ``"vmem"`` (the kernel sees ``block``, the block the grid
    indices in ``grid_axes`` pick), ``"smem"`` (whole array) or ``"hbm"``
    (whole array, read by DMA).  While planning ``ref`` is None and every
    access is logged instead; ``scalar`` marks a 0-d leaf stored as (1,)."""

    def __init__(self, shape, dtype, scalar: bool = False):
        self.shape = tuple(shape)
        self.dtype = jnp.dtype(dtype)
        self.scalar = scalar
        self.mem = "vmem"
        self.block = self.shape
        self.grid_axes: Tuple[Optional[int], ...] = (None,) * len(self.shape)
        self.log: List[Tuple[tuple, tuple, bool]] = []
        self.ref = None
        self.origin: Tuple[Aff, ...] = (Aff(0),) * len(self.shape)
        self.ctx = None

    def view(self) -> "_RefView":
        if self.scalar:
            return _RefView(self, (Aff(0),), ())
        return _RefView(self, (Aff(0),) * len(self.shape),
                        tuple((p, 1, n) for p, n in enumerate(self.shape)))

    def bind(self, ref, ctx, grid_syms: Sequence[_Sym]) -> None:
        self.ref, self.ctx = ref, ctx
        self.origin = tuple(
            Aff(0) if a is None else Aff.sym(grid_syms[a])._scaled(b)
            for a, b in zip(self.grid_axes, self.block))

    # -- access ----------------------------------------------------------

    def read(self, base: Tuple[Aff, ...],
             axes: Tuple[Tuple[int, int, int], ...]):
        rank = len(self.shape)
        on: List[List[Tuple[int, int, int]]] = [[] for _ in range(rank)]
        for k, (p, s, n) in enumerate(axes):
            on[p].append((s, n, k))
        spans = [1 + sum((n - 1) * s for s, n, _ in on[p]) if on[p] else 1
                 for p in range(rank)]
        if self.ref is None:
            self.log.append((base, tuple(spans), not axes))
            self.ctx.reads.append((self, tuple(spans)))
            return jnp.zeros(tuple(n for _, _, n in axes), self.dtype)
        idx = []
        for p in range(rank):
            off = (base[p] - self.origin[p]).start()
            idx.append(pl.ds(off, spans[p]) if on[p] else off)
        if self.mem == "hbm":
            buf, sem = self.ctx.next_dma(self)
            src = tuple(i if isinstance(i, pl.Slice) else pl.ds(i, 1)
                        for i in idx)
            cp = pltpu.make_async_copy(self.ref.at[src], buf, sem)
            cp.start()
            cp.wait()
            v = buf[...].reshape(tuple(spans[p] for p in range(rank) if on[p]))
        else:
            v = self.ref[tuple(idx)]
        # phys axes -> logical axes: unflatten each axis, then permute
        dims, labels, d = [], [], 0
        for p in range(rank):
            ax = sorted(on[p], key=lambda t: -t[0])
            if not ax:
                continue
            if spans[p] != math.prod(n for _, n, _ in ax):  # strided view
                offs = np.zeros((), np.int32)
                for s, n, _ in ax:
                    offs = offs[..., None] + s * np.arange(n, dtype=np.int32)
                v = jnp.take(v, jnp.asarray(offs.reshape(-1)), axis=d)
            d += 1
            dims += [n for _, n, _ in ax]
            labels += [k for _, _, k in ax]
        if tuple(dims) != v.shape:
            v = v.reshape(tuple(dims))
        perm = [labels.index(k) for k in range(len(axes))]
        return v if perm == sorted(perm) else jnp.transpose(v, perm)

    def write(self, path: Sequence, value) -> None:
        rank = len(self.shape)
        base, ext = [], []
        if not self.scalar:
            for c in path:
                if isinstance(c, tuple) and c[0] == "ds":
                    base.append(Aff.of(c[1]))
                    ext.append(c[2])
                else:
                    base.append(Aff.of(c))
                    ext.append(None)
        for p in range(len(base), rank):
            base.append(Aff(0))
            ext.append(None if self.scalar else self.shape[p])
        if self.ref is None:
            self.log.append((tuple(base), tuple(e or 1 for e in ext),
                             all(e is None for e in ext)))
            return
        idx = tuple(pl.ds((b - o).start(), e) if e is not None
                    else (b - o).start()
                    for b, o, e in zip(base, self.origin, ext))
        value = jnp.asarray(value, self.dtype)
        shape = tuple(e for e in ext if e is not None)
        self.ref[idx] = value if value.shape == shape else value.reshape(shape)

    # -- placement (from the planning log) ---------------------------------

    def place(self, is_output: bool) -> None:
        rank = len(self.shape)
        block, axes = list(self.shape), [None] * rank
        for p in range(rank):
            g = _block_along([(b[p], e[p]) for b, e, _ in self.log],
                             self.shape[p])
            if g is not None:
                axes[p], block[p] = g
        # Mosaic tiling: the trailing block dims are whole or whole tiles;
        # a 1-D block is a whole number of (sublane x lane) tiles
        sub = _sublanes(self.dtype)
        tiles = [sub * 128] if rank == 1 else [sub, 128]
        for p, t in zip(range(rank - len(tiles), rank), tiles):
            if block[p] != self.shape[p] and block[p] % t:
                block[p], axes[p] = self.shape[p], None
        axes = [a if b != n else None
                for a, b, n in zip(axes, block, self.shape)]
        nbytes = math.prod(block) * self.dtype.itemsize
        if (self.log and all(scalar for _, _, scalar in self.log)
                and axes == [None] * rank and self.dtype.itemsize == 4
                and nbytes <= SMEM_BYTES):
            self.mem = "smem"
        elif is_output or 2 * nbytes <= VMEM_OPERAND_BYTES:
            self.mem = "vmem"
        else:
            self.mem = "hbm"
            block, axes = list(self.shape), [None] * rank
        self.block, self.grid_axes = tuple(block), tuple(axes)

    def refusal(self) -> Optional[str]:
        """Why Mosaic cannot lower this leaf's logged accesses, or None.

        Memory holds (sublane x lane) tiles.  No leaf is accessed one
        element at a time; along a tiled axis an access spans its whole
        block, or whole tiles at a tile-aligned offset.  A 1-D leaf is
        tiled in units of sublanes x lanes; a DMA from HBM slices both of
        the trailing axes by tiles, a VMEM load only the lane axis."""
        if self.mem == "smem":
            return None
        rank, sub = len(self.shape), _sublanes(self.dtype)
        if rank == 1:
            tiles = {0: sub * 128}
        else:
            tiles = {rank - 1: 128}
            if self.mem == "hbm":
                tiles[rank - 2] = sub
        where = f"{self.mem.upper()} array {self.shape} {self.dtype.name}"
        for base, spans, scalar in self.log:
            if scalar:
                return f"it accesses a {where} one element at a time"
            for p, tile in tiles.items():
                axis, block = self.grid_axes[p], self.block[p]
                off = [c for s, c in base[p].terms.items()
                       if s.grid is None or s.grid != axis or c != block]
                if not off and base[p].const == 0 and spans[p] == block:
                    continue
                if spans[p] % tile or any(c % tile
                                          for c in off + [base[p].const]):
                    return (f"it accesses {spans} elements of a {where} at "
                            f"a time: axis {p} is not whole {tile}-element "
                            f"tiles at tile-aligned offsets")
        return None

    def spec(self) -> pl.BlockSpec:
        if self.mem == "smem":
            return pl.BlockSpec(memory_space=pltpu.SMEM)
        if self.mem == "hbm":
            return pl.BlockSpec(memory_space=pl.ANY)
        axes = self.grid_axes
        return pl.BlockSpec(
            self.block,
            lambda *g: tuple(0 if a is None else g[a] for a in axes))


def _block_along(accesses, dim: int) -> Optional[Tuple[int, int]]:
    """(grid axis, block size) when every access of one phys axis is
    ``size * g + r`` for one grid index ``g`` with ``r + extent <= size``."""
    found = None
    for base, extent in accesses:
        grid = [(s, c) for s, c in base.terms.items() if s.grid is not None]
        if len(grid) != 1:
            return None
        s, c = grid[0]
        if found is None:
            found = (s.grid, c)
        elif found != (s.grid, c):
            return None
        rest = (base - Aff.sym(s)._scaled(c)).bounds()
        if rest is None or rest[0] < 0 or rest[1] + extent > c:
            return None
    if found is None or found[1] <= 0 or dim % found[1]:
        return None
    return found


class _RefView(Lazy):
    """A lazy view of a :class:`_Buf`: logical axes as (phys axis, stride,
    size) and one affine base offset per phys axis."""

    def __init__(self, buf: _Buf, base: Tuple[Aff, ...],
                 axes: Tuple[Tuple[int, int, int], ...]):
        self.buf, self.base, self.axes = buf, base, axes

    def split(self, n: int):
        (p, s, m), rest = self.axes[0], self.axes[1:]
        return _RefView(self.buf, self.base,
                        ((p, s * n, m // n), (p, s, n)) + rest)

    def join(self):
        (p, s1, k), (q, s2, m) = self.axes[:2]
        if p != q or s1 != s2 * m:
            v = self.load()
            return v.reshape((k * m,) + v.shape[2:])
        return _RefView(self.buf, self.base, ((p, s2, k * m),) + self.axes[2:])

    def index(self, i):
        (p, s, _), rest = self.axes[0], self.axes[1:]
        base = list(self.base)
        base[p] = base[p] + Aff.of(i)._scaled(s)
        return _RefView(self.buf, tuple(base), rest)

    def transpose(self):
        a0, a1 = self.axes[:2]
        return _RefView(self.buf, self.base, (a1, a0) + self.axes[2:])

    def load(self):
        return self.buf.read(self.base, self.axes)


def _bufs_of(refs):
    return jax.tree_util.tree_leaves(
        refs, is_leaf=lambda x: isinstance(x, _Buf))


def _buf_write(refs, path, value) -> None:
    """Write ``value`` into the buffer tree ``refs`` at ``path``."""
    if isinstance(refs, tuple):
        for k, comp in enumerate(path):
            if comp in (FST, SND):
                _buf_write(refs[0 if comp == FST else 1],
                           list(path[:k]) + list(path[k + 1:]), value)
                return
        for r, v in zip(refs, value):
            _buf_write(r, path, v)
        return
    refs.write(path, value)


# ---------------------------------------------------------------------------
# the kernel-body executor
# ---------------------------------------------------------------------------

class _RefStore:
    """dict-like store resolving imperative variables to views of their
    buffers, so the functional interpreter (Fig. 6c evaluator) reads them
    through the same index arithmetic as inputs."""

    def __init__(self, refs: Dict[str, object]):
        self.refs = refs

    def __contains__(self, name):
        return name in self.refs

    def __getitem__(self, name):
        return jax.tree_util.tree_map(
            lambda b: b.view(), self.refs[name],
            is_leaf=lambda x: isinstance(x, _Buf))


class _KernelCtx:
    """State while tracing one kernel body (planning or emitting)."""

    def __init__(self, kenv, refs, bindings, scratch_iter):
        self.kenv = kenv            # name -> value, index or REG cell
        self.refs = refs            # name -> _Buf pytree (outputs, scratch)
        self.bindings = bindings    # acceptor-parameter name -> acceptor
        self.scratch_iter = scratch_iter
        self.reg_names = set()
        self.reads: List[Tuple[_Buf, tuple]] = []   # planning: in order
        self.dma = iter(())                         # emitting: HBM reads

    def eval(self, e):
        return force(interp(e, self.kenv, _RefStore(self.refs)))

    def index(self, e):
        return interp(e, self.kenv, _RefStore(self.refs))

    def next_dma(self, buf: _Buf):
        owner, scratch, sem = next(self.dma)
        assert owner is buf, "DMA reads out of planning order"
        return scratch, sem


def _exec_kernel(p: P.Phrase, ctx: _KernelCtx) -> None:  # noqa: C901
    if isinstance(p, P.Skip):
        return
    if isinstance(p, P.SeqC):
        _exec_kernel(p.c1, ctx)
        _exec_kernel(p.c2, ctx)
        return
    if isinstance(p, P.Assign):
        value = ctx.eval(p.e)
        _kwrite(p.a, [], value, ctx)
        return
    if isinstance(p, P.New):
        v = P.Var(P.fresh("kbuf"), VarT(p.d))
        if p.space == P.REG:
            ctx.kenv[v.name] = zero_value(p.d)
            ctx.reg_names.add(v.name)
            _exec_kernel(p.f(v), ctx)
            ctx.kenv.pop(v.name, None)
            ctx.reg_names.discard(v.name)
        else:  # vmem (and any hbm remnants) -> scratch refs
            refs = next(ctx.scratch_iter)
            ctx.refs[v.name] = refs
            _exec_kernel(p.f(v), ctx)
            del ctx.refs[v.name]
        return
    if isinstance(p, P.For):
        i = P.Var(P.fresh("i"), ExpT(Idx(p.n)))
        body = p.f(i)
        if p.unroll:
            for k in range(p.n):
                ctx.kenv[i.name] = Aff(k)
                _exec_kernel(body, ctx)
            ctx.kenv.pop(i.name, None)
            return
        _kernel_loop(p.n, i, body, ctx)
        return
    if isinstance(p, P.ParFor):
        # deeper parallel loops inside a kernel run sequentially on this core
        # (the strategy put them below the grid level on purpose)
        i = P.Var(P.fresh("i"), ExpT(Idx(p.n)))
        o = P.Var(P.fresh("o"), AccT(p.d))
        ctx.bindings[o.name] = P.IdxAcc(p.a, i)
        _kernel_loop(p.n, i, p.f(i, o), ctx)
        ctx.bindings.pop(o.name, None)
        return
    if isinstance(p, (P.MapI, P.ReduceI)):
        _exec_kernel(stage2.expand(p), ctx)
        return
    raise TypeError(f"_exec_kernel: not a command {type(p).__name__}")


def _kernel_loop(n: int, i: P.Var, body: P.Phrase, ctx: _KernelCtx) -> None:
    """``body`` for i in [0, n) as a fori_loop carrying the REG cells it
    writes; ``i`` is a symbolic index so reads stay affine."""
    regs = sorted(r for r in written_roots(body) if r in ctx.reg_names)

    def loop_body(k, carry):
        ctx.kenv[i.name] = Aff.sym(_Sym(k, n=n))
        for r, c in zip(regs, carry):
            ctx.kenv[r] = c
        _exec_kernel(body, ctx)
        return tuple(ctx.kenv[r] for r in regs)

    final = jax.lax.fori_loop(0, n, loop_body,
                              tuple(ctx.kenv[r] for r in regs))
    for r, c in zip(regs, final):
        ctx.kenv[r] = c
    ctx.kenv.pop(i.name, None)


def _kwrite(a: P.Phrase, idxs: List, value, ctx: _KernelCtx) -> None:
    """In-kernel acceptor write: REG cells rebind, buffers store."""
    # chase bound acceptor parameters (the o of each enclosing parfor)
    while isinstance(a, P.Var) and a.name in ctx.bindings:
        a = ctx.bindings[a.name]

    def leaf(root, path, val):
        if isinstance(root, P.Var):
            name = root.name
        else:  # AccPart
            name = root.v.name
        if name in ctx.bindings:
            _kwrite(ctx.bindings[name], path, val, ctx)
            return None
        if name in ctx.reg_names:
            ctx.kenv[name] = set_path(ctx.kenv[name], _concrete(path), val)
            return None
        if name in ctx.refs:
            _buf_write(ctx.refs[name], path, val)
            return None
        raise KeyError(f"kernel write to unknown root {name!r}")

    fold_acc(a, idxs, value, ctx.index, leaf)


# ---------------------------------------------------------------------------
# kernel stage construction
# ---------------------------------------------------------------------------

def _collect_grid(pf: P.ParFor):
    """Peel nested grid parfors; returns (grid_dims, i_vars, body, out_acc)."""
    dims: List[int] = []
    ivars: List[P.Var] = []
    bindings: Dict[str, P.Phrase] = {}
    node: P.Phrase = pf
    while isinstance(node, P.ParFor) and node.level.kind in ("grid", "par"):
        i = P.Var(P.fresh("g"), ExpT(Idx(node.n)))
        o = P.Var(P.fresh("go"), AccT(node.d))
        dims.append(node.n)
        ivars.append(i)
        body = node.f(i, o)
        bindings[o.name] = P.IdxAcc(node.a, i)
        node = body
    return dims, ivars, node, bindings


def _free_exp_vars(p: P.Phrase) -> Dict[str, DataType]:
    """Free expression-typed identifiers of a phrase (kernel inputs)."""
    found: Dict[str, DataType] = {}

    def go(q, bound):
        if isinstance(q, P.Var) and isinstance(q.t, ExpT):
            if q.name not in bound:
                found[q.name] = q.t.d
            return
        if isinstance(q, P.ExpPart) and isinstance(q.v, P.Var):
            if q.v.name not in bound:
                found[q.v.name] = q.v.t.d
            return
        for attr in ("e", "a", "b", "i", "v", "c1", "c2", "init", "acc", "exp"):
            c = getattr(q, attr, None)
            if isinstance(c, P.Phrase):
                go(c, bound)
        # binders
        if isinstance(q, P.New):
            v = P.Var(P.fresh("v"), VarT(q.d))
            go(q.f(v), bound | {v.name})
        elif isinstance(q, P.For):
            i = P.Var(P.fresh("i"), ExpT(Idx(q.n)))
            go(q.f(i), bound | {i.name})
        elif isinstance(q, P.ParFor):
            i = P.Var(P.fresh("i"), ExpT(Idx(q.n)))
            o = P.Var(P.fresh("o"), AccT(q.d))
            go(q.f(i, o), bound | {i.name, o.name})
        elif isinstance(q, P.Map):
            d = P.exp_data(q.e)
            x = P.Var(P.fresh("x"), ExpT(d.elem))
            go(q.f(x), bound | {x.name})
        elif isinstance(q, P.Reduce):
            d = P.exp_data(q.e)
            x = P.Var(P.fresh("x"), ExpT(d.elem))
            acc = P.Var(P.fresh("acc"), P.type_of(q.init))
            go(q.f(x, acc), bound | {x.name, acc.name})
        elif isinstance(q, (P.MapI, P.ReduceI)):
            go(stage2.expand(q), bound)

    go(p, set())
    return found


def _collect_scratch(body: P.Phrase) -> List[DataType]:
    """Data types of non-REG News in deterministic traversal order."""
    out: List[DataType] = []

    def go(q):
        if isinstance(q, P.SeqC):
            go(q.c1)
            go(q.c2)
        elif isinstance(q, P.New):
            if q.space != P.REG:
                out.append(q.d)
            go(q.f(P.Var(P.fresh("v"), VarT(q.d))))
        elif isinstance(q, P.For):
            go(q.f(P.Var(P.fresh("i"), ExpT(Idx(q.n)))))
        elif isinstance(q, P.ParFor):
            go(q.f(P.Var(P.fresh("i"), ExpT(Idx(q.n))),
                   P.Var(P.fresh("o"), AccT(q.d))))
        elif isinstance(q, (P.MapI, P.ReduceI)):
            go(stage2.expand(q))

    go(body)
    return out


def _leaf_buf(x) -> _Buf:
    return _Buf(x.shape or (1,), x.dtype, scalar=not x.shape)


def _run_kernel_stage(pf: P.ParFor, env: Dict, store: Store,
                      interpret: bool) -> Store:
    dims, ivars, body, bindings = _collect_grid(pf)
    root = acc_root(pf.a)
    out_buf = store[root]

    # kernel inputs: expression identifiers from env (arguments, enclosing
    # host loop indices) or from the store (host temporaries)
    in_names = [n for n in sorted(_free_exp_vars(body))
                if n in env or n in store]
    in_vals = [env[n] if n in env else store[n] for n in in_names]
    in_trees = [jax.tree_util.tree_map(_leaf_buf, v) for v in in_vals]
    in_bufs = [b for t in in_trees for b in _bufs_of(t)]
    flat_in = [jnp.reshape(l, (1,)) if l.ndim == 0 else l
               for v in in_vals for l in jax.tree_util.tree_leaves(v)]

    out_leaves, out_treedef = jax.tree_util.tree_flatten(out_buf)
    out_bufs = [_leaf_buf(l) for l in out_leaves]
    out_tree = jax.tree_util.tree_unflatten(out_treedef, out_bufs)

    scratch_types = _collect_scratch(body)
    scratch_bufs = [[_Buf(s or (1,), dt, scalar=not s)
                     for s, dt in _flat_leaf_shapes(d)] for d in scratch_types]

    all_bufs = in_bufs + out_bufs + [b for bs in scratch_bufs for b in bs]

    def trace(grid_syms, bind):
        """Run the body once; ``bind(ctx)`` attaches the buffers."""
        kenv = {name: _RefStore({name: t})[name]
                for name, t in zip(in_names, in_trees)}
        for iv, s in zip(ivars, grid_syms):
            kenv[iv.name] = Aff.sym(s)
        ctx = _KernelCtx(kenv, {root: out_tree}, dict(bindings),
                         iter(_build_ref_tree(d, iter(bs))
                              for d, bs in zip(scratch_types, scratch_bufs)))
        bind(ctx)
        _exec_kernel(body, ctx)
        return ctx

    def plan(ctx):
        for b in all_bufs:
            b.ctx = ctx

    # planning: trace abstractly, logging every access, then place each leaf
    planned = []
    jax.make_jaxpr(lambda: planned.append(trace(
        [_Sym(jnp.int32(0), n, grid=k) for k, n in enumerate(dims)],
        plan)))()
    for b in in_bufs:
        b.place(is_output=False)
    for b in out_bufs:
        b.place(is_output=True)
    if not interpret:
        for b in all_bufs:
            why = b.refusal()
            if why:
                raise NotImplementedError(
                    f"this strategy does not lower to a TPU kernel: {why}")
    dma_reads = [(b, shape) for b, shape in planned[0].reads if b.mem == "hbm"]

    def kernel(*refs):
        # refs: inputs, outputs, scratch, then DMA buffers and their
        # semaphore — the order of all_bufs and dma_reads
        grid_syms = [_Sym(pl.program_id(k), n, grid=k)
                     for k, n in enumerate(dims)]

        def bind(ctx):
            for b, r in zip(all_bufs, refs):
                b.bind(r, ctx, grid_syms)
            ctx.dma = iter([(b, r, refs[-1]) for (b, _), r in
                            zip(dma_reads, refs[len(all_bufs):])])

        trace(grid_syms, bind)

    scratch_shapes = [pltpu.VMEM(b.shape, b.dtype)
                      for bs in scratch_bufs for b in bs]
    scratch_shapes += [pltpu.VMEM(shape, b.dtype) for b, shape in dma_reads]
    if dma_reads:
        scratch_shapes.append(pltpu.SemaphoreType.DMA(()))
    extra = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=VMEM_LIMIT_BYTES)}
    result = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct(b.shape, b.dtype) for b in out_bufs],
        grid=tuple(dims) if dims else (1,),
        in_specs=[b.spec() for b in in_bufs],
        out_specs=[b.spec() for b in out_bufs],
        scratch_shapes=scratch_shapes,
        interpret=interpret,
        **extra,
    )(*flat_in)
    result = [r.reshape(l.shape) for r, l in zip(result, out_leaves)]
    out_store = dict(store)
    out_store[root] = jax.tree_util.tree_unflatten(out_treedef, result)
    return out_store


# ---------------------------------------------------------------------------
# host-side executor: like stage3_jnp.exec_comm but grid parfors -> kernels
# ---------------------------------------------------------------------------

def exec_host(p: P.Phrase, env: Dict, store: Store, interpret: bool) -> Store:
    if isinstance(p, P.ParFor) and p.level.kind in ("grid", "par"):
        return _run_kernel_stage(p, env, store, interpret)
    if isinstance(p, P.SeqC):
        return exec_host(p.c2, env,
                         exec_host(p.c1, env, store, interpret), interpret)
    if isinstance(p, P.New):
        v = P.Var(P.fresh("hbuf"), VarT(p.d))
        store2 = dict(store)
        store2[v.name] = zero_value(p.d)
        store3 = exec_host(p.f(v), env, store2, interpret)
        store3 = dict(store3)
        del store3[v.name]
        return store3
    if isinstance(p, (P.MapI, P.ReduceI)):
        return exec_host(stage2.expand(p), env, store, interpret)
    # everything else (assignments, sequential loops) runs host-side
    return exec_comm(p, env, store)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def compile_expr_pallas(expr: P.Phrase, arg_vars, *,
                        interpret: Optional[bool] = None,
                        check: bool = True, lowered=None,
                        name: str = "dpia-pallas kernel"):
    """Functional expression -> callable running grid strategies as Pallas
    kernels (Stage I -> II -> kernel extraction).  ``lowered`` optionally
    supplies an already-translated ``(command, out_var)`` pair (the staged
    repro.compiler path) so Stage I/II is not redone here.  ``interpret``
    None picks interpret mode only on the CPU.  With ``interpret=False`` a
    strategy whose accesses Mosaic cannot tile raises NotImplementedError
    naming the program (``name``) when the callable is traced."""
    from . import check as chk
    from . import hoist as hoist_mod

    if interpret is None:
        from repro.compiler.options import default_interpret
        interpret = default_interpret()
    if lowered is not None:
        cmd, out = lowered
        d = out.t.d
    else:
        d = P.exp_data(expr)
        out = P.Var("out#", AccT(d))
        cmd = stage2.expand(stage1.translate(expr, out))
    # SCIR check happens BEFORE hoisting (as in the paper, where section 6.4 is
    # a code-generation step downstream of the type system; hoisting preserves
    # race freedom by construction — each iteration owns its indexed slice).
    if check:
        P.type_of(cmd)
        chk.check_race_free(cmd)
    # paper 6.4: HBM temporaries must be allocated outside kernels
    cmd = hoist_mod.hoist(cmd, spaces=(P.HBM,))
    names = [v.name for v in arg_vars]
    out_name = out.name

    def fn(*args):
        env = dict(zip(names, args))
        store: Store = {out_name: zero_value(d)}
        try:
            store = exec_host(cmd, env, store, interpret)
        except NotImplementedError as e:
            raise NotImplementedError(f"{name}: {e}") from None
        return store[out_name]

    return fn


# self-register as a Stage III target (see repro.compiler.backends)
from repro.compiler.backends import Backend as _Backend  # noqa: E402
from repro.compiler.backends import register_backend as _register  # noqa: E402

_register(_Backend(
    name="pallas", compile=compile_expr_pallas,
    accepts=("check", "lowered", "interpret", "name"),
    description="grid-level imperative DPIA -> pl.pallas_call kernels (TPU; "
                "interpret mode on CPU)"),
    aliases=("dpia-pallas",), overwrite=True)
