"""Collective communication API.

Reference surface: ``python/paddle/distributed/collective.py`` (all_reduce
``:711``, all_gather ``:915``, alltoall ``:1844``, send/recv ``:2033/:2096``,
reduce_scatter ``:2413``…) executing through ProcessGroupNCCL / ``c_*``
collective ops over NCCL rings.

TPU-native redesign (SURVEY.md §5 "Distributed communication backend"): a
group is a named axis of a ``jax.sharding.Mesh``; each collective IS the
corresponding XLA HLO collective:

    c_allreduce_sum  ≙ lax.psum          c_allgather ≙ lax.all_gather
    c_reducescatter  ≙ lax.psum_scatter  alltoall    ≙ lax.all_to_all
    c_broadcast      ≙ select+psum       send/recv_v2≙ lax.ppermute

Execution contexts:
  1. Inside an spmd region (``shard_map`` / pjit trace) — the normal case,
     analogous to ``c_*`` ops inside a Program: lower directly to the lax
     collective on the group's axis name.
  2. Eager, on a Tensor whose array is sharded over the group's mesh axis —
     analogous to a dygraph ProcessGroup call: wrap the lax collective in a
     one-op ``shard_map`` and run it (single-controller: all "ranks" of the
     group live in this process as shards).

There is no stream management, no comm-context cache, no bucketing: XLA
schedules/overlaps collectives itself (the Reducer machinery of
``imperative/reducer.h:129`` is intentionally absent).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from ..framework.tensor import Tensor
from . import mesh as mesh_mod

__all__ = [
    "ReduceOp",
    "Group",
    "new_group",
    "get_group",
    "is_initialized",
    "all_reduce",
    "all_gather",
    "all_gather_object",
    "all_to_all",
    "alltoall",
    "alltoall_single",
    "broadcast",
    "reduce",
    "reduce_scatter",
    "scatter",
    "send",
    "recv",
    "isend",
    "irecv",
    "barrier",
    "wait",
    "stream_sync",
]


class ReduceOp:
    """reference ``distributed/collective.py ReduceOp`` (SUM/MAX/MIN/PROD/AVG)."""

    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3
    AVG = 4


class Group:
    """A communication group = (mesh, axis_name) + member ranks.

    Reference ``collective.py Group`` held a ProcessGroup ptr + ring id; here
    the mesh axis plays the ring and XLA owns the transport.
    """

    def __init__(self, mesh: Mesh, axis_name: str, ranks=None, gid=0):
        self.mesh = mesh
        self.axis_name = axis_name
        self.id = gid
        ax = mesh.axis_names.index(axis_name)
        self.nranks = mesh.devices.shape[ax]
        self.ranks = list(ranks) if ranks is not None else list(range(self.nranks))

    @property
    def world_size(self):
        return self.nranks

    @property
    def rank(self):
        # single-controller: the "current rank" only exists inside an spmd
        # region, where it is the *traced* axis_index (do not force it to a
        # python int — that would concretize the tracer); outside we report
        # 0 (the controller).
        try:
            return lax.axis_index(self.axis_name)
        except Exception:
            return 0

    def get_group_rank(self, rank):
        return self.ranks.index(rank) if rank in self.ranks else -1

    def process_group(self):  # API-parity shim
        return self

    def __repr__(self):
        return f"Group(axis={self.axis_name!r}, nranks={self.nranks}, id={self.id})"


_GROUPS: dict[int, Group] = {}
_NEXT_GID = [1]


def _default_group() -> Group:
    """The WORLD group: all devices on one axis. Built on its own 1-axis
    mesh — independent of any hybrid mesh installed by fleet.init, whose
    first axis (pp) would otherwise masquerade as the world ring."""
    if 0 not in _GROUPS:
        m = mesh_mod.build_mesh({"world": len(jax.devices())})
        _GROUPS[0] = Group(m, "world", gid=0)
    return _GROUPS[0]


def is_initialized():
    return 0 in _GROUPS or mesh_mod.get_mesh() is not None


def new_group(ranks=None, backend=None, timeout=None, axis_name=None, mesh=None):
    """reference ``collective.py:366 new_group``. TPU: a new group is a mesh
    axis — either an axis of the current global mesh (``axis_name=``) or a
    fresh 1-axis mesh over ``ranks`` device ids."""
    gid = _NEXT_GID[0]
    _NEXT_GID[0] += 1
    if mesh is not None and axis_name is not None:
        g = Group(mesh, axis_name, gid=gid)
    elif axis_name is not None:
        m = mesh_mod.get_mesh() or mesh_mod.default_mesh()
        g = Group(m, axis_name, gid=gid)
    else:
        devs = jax.devices()
        sel = [devs[r] for r in ranks] if ranks else devs
        m = Mesh(np.array(sel), axis_names=("_g%d" % gid,))
        g = Group(m, "_g%d" % gid, ranks=ranks, gid=gid)
    _GROUPS[gid] = g
    return g


def get_group(gid=0):
    return _GROUPS.get(gid, _default_group() if gid == 0 else None)


# ---------------------------------------------------------------------------
# execution helpers
# ---------------------------------------------------------------------------

def _in_spmd(axis_name) -> bool:
    """True when called under a trace with ``axis_name`` bound (shard_map)."""
    try:
        lax.axis_index(axis_name)
        return True
    except (NameError, TypeError):
        return False
    except Exception:
        return False


def _unwrap(t):
    return t._value if isinstance(t, Tensor) else jnp.asarray(t)


def _apply(tensor, group, per_shard_fn, out_specs=None, in_specs=None):
    """Run ``per_shard_fn`` for tensor: direct when already inside an spmd
    region; otherwise as a one-op shard_map over the group's mesh axis
    (the eager ProcessGroup path)."""
    g = group or _default_group()
    x = _unwrap(tensor)
    if _in_spmd(g.axis_name):
        return per_shard_fn(x)
    if g.nranks == 1:
        return per_shard_fn_single(per_shard_fn, x, g)
    ins = in_specs if in_specs is not None else P(g.axis_name)
    outs = out_specs if out_specs is not None else P(g.axis_name)
    fn = shard_map(
        per_shard_fn, mesh=g.mesh, in_specs=(ins,), out_specs=outs, check_vma=False
    )
    return fn(x)


def per_shard_fn_single(fn, x, g):
    """world_size==1: run the collective body with the axis bound to size 1.
    The axis is bound by a size-1 ``vmap``, not by a one-device mesh: the
    data stays on whatever device (or sharding) it lives on, where a mesh
    over ``jax.devices()[:1]`` would pull everything onto chip 0."""
    return jax.tree_util.tree_map(
        lambda o: o[0], jax.vmap(fn, axis_name=g.axis_name)(x[None]))


def _mp_eager(g, x):
    """True when running real multi-controller (``jax.process_count() > 1``),
    the group spans all processes, and ``x`` is a process-local array. Eager
    collectives then use CROSS-PROCESS semantics — each process contributes
    its local value, exactly the reference's per-rank NCCL behavior — via
    jax.experimental.multihost_utils, instead of the single-controller
    stacked-global convention documented on each function."""
    import jax

    try:
        n = jax.process_count()
    except Exception:
        return False
    if n <= 1 or g.nranks != n or _in_spmd(g.axis_name):
        return False
    return bool(getattr(x, "is_fully_addressable", True))


def _mp_axis_reduce(op, stacked):
    if op == ReduceOp.SUM:
        return jnp.sum(stacked, axis=0)
    if op == ReduceOp.MAX:
        return jnp.max(stacked, axis=0)
    if op == ReduceOp.MIN:
        return jnp.min(stacked, axis=0)
    if op == ReduceOp.PROD:
        return jnp.prod(stacked, axis=0).astype(stacked.dtype)
    if op == ReduceOp.AVG:
        return jnp.mean(stacked, axis=0)
    raise ValueError(f"unknown ReduceOp {op}")


def _op_suffix(op):
    return {ReduceOp.SUM: "sum", ReduceOp.MAX: "max", ReduceOp.MIN: "min",
            ReduceOp.PROD: "prod", ReduceOp.AVG: "avg"}.get(op, "sum")


def _reduce_fn(op, axis):
    if op == ReduceOp.SUM:
        return lambda x: lax.psum(x, axis)
    if op == ReduceOp.MAX:
        return lambda x: lax.pmax(x, axis)
    if op == ReduceOp.MIN:
        return lambda x: lax.pmin(x, axis)
    if op == ReduceOp.PROD:
        return lambda x: jnp.prod(
            lax.all_gather(x, axis, tiled=False), axis=0
        ).astype(x.dtype)
    if op == ReduceOp.AVG:
        return lambda x: lax.pmean(x, axis)
    raise ValueError(f"unknown ReduceOp {op}")


def _ret(tensor, val):
    """Collectives mutate in place (reference dygraph semantics) and return
    the tensor for chaining."""
    if isinstance(tensor, Tensor):
        tensor._value = val
        return tensor
    return Tensor(val)


def _record_static(opname, g, per_shard_fn, tensor, in_specs=None,
                   out_specs=None):
    """Record the collective into the active static Program.

    Reference: the ``c_*`` collective op set appended to a BlockDesc
    (``operators/collective/c_allreduce_op.h:364``) so a serialized static
    Program can carry and replay communication — SURVEY §7's last hard
    part.  Here the recorded fwd is the same one-op ``shard_map`` the eager
    path runs; the Executor replays it under its jit (and
    ``save_inference_model`` serializes it into the StableHLO artifact,
    collectives included).  Returns the output Variable, or None when not
    recording / ``tensor`` is not symbolic."""
    from ..ops import dispatch

    if dispatch.STATIC_RECORDER is None:
        return None
    from ..static.program import Variable

    if not isinstance(tensor, Variable):
        return None
    ins = in_specs if in_specs is not None else P(g.axis_name)
    outs = out_specs if out_specs is not None else P(g.axis_name)

    def fwd(x):
        if g.nranks == 1:
            return per_shard_fn_single(per_shard_fn, x, g)
        return shard_map(per_shard_fn, mesh=g.mesh, in_specs=(ins,),
                         out_specs=outs, check_vma=False)(x)

    return dispatch.apply_op(opname, fwd, (tensor,), {})


# ---------------------------------------------------------------------------
# collectives
# ---------------------------------------------------------------------------

def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """reference ``collective.py:711`` / ``c_allreduce_op.h:364`` ≙ psum.

    Eager semantics: tensor is sharded over the group axis; every shard is
    replaced by the reduction of all shards (so the array becomes replicated
    along the axis — same postcondition as NCCL allreduce over ranks).
    """
    g = group or _default_group()
    body = _reduce_fn(op, g.axis_name)
    rec = _record_static(f"c_allreduce_{_op_suffix(op)}", g, body, tensor)
    if rec is not None:
        return tensor._rebind(rec)
    if _in_spmd(g.axis_name):
        return _ret(tensor, body(_unwrap(tensor)))
    x = _unwrap(tensor)
    if _mp_eager(g, x):
        from jax.experimental import multihost_utils as mhu

        stacked = mhu.process_allgather(x, tiled=False)  # [nproc, ...]
        return _ret(tensor, _mp_axis_reduce(op, jnp.asarray(stacked)))
    # eager: shards go in per-rank, reduced value comes out replicated
    val = _apply(tensor, g, body, in_specs=P(g.axis_name), out_specs=P(g.axis_name))
    # result is identical on every shard slice; collapse back to the
    # original (unstacked per-rank) shape by taking shard 0's view: the
    # array was stacked along dim0 by convention of the eager path.
    return _ret(tensor, val)


def all_gather(tensor_list, tensor=None, group=None, sync_op=True, axis=0):
    """reference ``collective.py:915`` ≙ lax.all_gather.

    In spmd regions: ``all_gather(None, x)`` returns the gathered array
    (stacked on dim0, tiled=False → new leading axis removed by reshape).
    Eager: appends per-rank shards to ``tensor_list``.
    """
    g = group or _default_group()
    if tensor is None and not isinstance(tensor_list, (list,)):
        tensor, tensor_list = tensor_list, None
    if tensor_list is None:
        # stacked-global eager convention: the global array already IS the
        # gather — record the identity so the Program carries the op
        rec = _record_static("c_allgather", g, lambda x: x, tensor,
                             in_specs=P(g.axis_name),
                             out_specs=P(g.axis_name))
        if rec is not None:
            return rec
    x = _unwrap(tensor)
    if _in_spmd(g.axis_name):
        out = lax.all_gather(x, g.axis_name, tiled=True)
        if tensor_list is not None:
            parts = jnp.split(out, g.nranks, axis=0)
            tensor_list.extend(Tensor(p) for p in parts)
            return tensor_list
        return Tensor(out)
    if _mp_eager(g, x):
        from jax.experimental import multihost_utils as mhu

        stacked = jnp.asarray(mhu.process_allgather(x, tiled=False))
        if tensor_list is not None:
            tensor_list.extend(Tensor(stacked[i]) for i in range(g.nranks))
            return tensor_list
        return Tensor(stacked.reshape((-1,) + tuple(stacked.shape[2:])))
    # eager sharded-array model: the global array already IS the
    # concatenation of per-rank shards, so the gather is an identity on
    # values; per-rank pieces are the dim0 chunks.
    if tensor_list is not None:
        parts = jnp.split(x, g.nranks, axis=0)
        tensor_list.extend(Tensor(p) for p in parts)
        return tensor_list
    return Tensor(x)


def all_gather_object(object_list, obj, group=None):
    """reference ``collective.py all_gather_object``. Single-controller: every
    rank holds the same python object."""
    g = group or _default_group()
    object_list.extend([obj] * g.nranks)
    return object_list


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    """reference ``collective.py:808`` — reduce to rank dst. XLA has no
    single-destination reduce; psum then mask (the compiler elides the dead
    branches on non-dst shards)."""
    g = group or _default_group()
    body = _reduce_fn(op, g.axis_name)

    def per_shard(x):
        r = body(x)
        idx = lax.axis_index(g.axis_name)
        return jnp.where(idx == dst, r, x)

    rec = _record_static(f"c_reduce_{_op_suffix(op)}", g, per_shard, tensor)
    if rec is not None:
        return tensor._rebind(rec)
    if _in_spmd(g.axis_name):
        return _ret(tensor, per_shard(_unwrap(tensor)))
    return _ret(tensor, _apply(tensor, g, per_shard))


def broadcast(tensor, src=0, group=None, sync_op=True):
    """reference ``collective.py:626`` / ``c_broadcast_op`` — rank src's
    value to all. ≙ mask + psum."""
    g = group or _default_group()

    def per_shard(x):
        idx = lax.axis_index(g.axis_name)
        contrib = jnp.where(idx == src, x, jnp.zeros_like(x))
        return lax.psum(contrib, g.axis_name)

    rec = _record_static("c_broadcast", g, per_shard, tensor)
    if rec is not None:
        return tensor._rebind(rec)
    if _in_spmd(g.axis_name):
        return _ret(tensor, per_shard(_unwrap(tensor)))
    xv = _unwrap(tensor)
    if _mp_eager(g, xv):
        import jax as _jax
        from jax.experimental import multihost_utils as mhu

        val = mhu.broadcast_one_to_all(
            xv, is_source=_jax.process_index() == src)
        return _ret(tensor, jnp.asarray(val))
    return _ret(tensor, _apply(tensor, g, per_shard))


def reduce_scatter(tensor, tensor_list=None, op=ReduceOp.SUM, group=None, sync_op=True):
    """reference ``collective.py:2413`` ≙ lax.psum_scatter.

    Forms: ``reduce_scatter(out, tensor_list)`` — every rank contributes the
    list (one entry per rank), rank i receives the reduction of entry i;
    ``reduce_scatter(x)`` with x stacked [nranks, ...] — rank i receives
    sum over ranks of row-piece i.
    """
    g = group or _default_group()
    if isinstance(tensor_list, (list, tuple)) and tensor_list:
        if len(tensor_list) != g.nranks:
            raise ValueError(
                f"reduce_scatter tensor_list needs {g.nranks} entries, got {len(tensor_list)}"
            )
        inp = jnp.stack([_unwrap(t) for t in tensor_list], axis=0)
        if _in_spmd(g.axis_name):
            return _ret(
                tensor,
                lax.psum_scatter(inp, g.axis_name, scatter_dimension=0, tiled=False),
            )
        # eager single-controller: all ranks contribute the same list, so
        # rank i's result is nranks * entry i; lay out stacked on the axis
        out = _apply(
            Tensor(inp),
            g,
            lambda x: lax.psum_scatter(x, g.axis_name, scatter_dimension=0, tiled=False)[None],
            in_specs=P(),
            out_specs=P(g.axis_name),
        )
        # stacked-global convention: row i = rank i's received piece
        return _ret(tensor, out)

    rec = _record_static(
        "c_reducescatter", g,
        lambda x: lax.psum_scatter(x[0], g.axis_name, scatter_dimension=0,
                                   tiled=True)[None],
        tensor)
    if rec is not None:
        return tensor._rebind(rec)
    inp = _unwrap(tensor)

    def per_shard(x):
        return lax.psum_scatter(x, g.axis_name, scatter_dimension=0, tiled=True)

    if _in_spmd(g.axis_name):
        return _ret(tensor, per_shard(inp))
    # eager: shard dim0 = rank dim; op applies to the rank's row
    out = _apply(Tensor(inp), g, lambda x: per_shard(x[0])[None])
    return _ret(tensor, out)


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    """reference ``collective.py:1014`` — src rank's list scattered to ranks.
    ≙ broadcast + per-rank slice (dynamic_slice on axis_index)."""
    g = group or _default_group()
    if tensor_list:
        full = jnp.stack([_unwrap(t) for t in tensor_list], axis=0)
    else:
        full = _unwrap(tensor)

    def per_shard(x, keep_rank_dim):
        idx = lax.axis_index(g.axis_name)
        contrib = jnp.where(idx == src, x, jnp.zeros_like(x))
        allx = lax.psum(contrib, g.axis_name)
        piece = lax.dynamic_slice_in_dim(allx, idx, 1, axis=0)
        return piece if keep_rank_dim else jnp.squeeze(piece, axis=0)

    if _in_spmd(g.axis_name):
        return _ret(tensor, per_shard(full, keep_rank_dim=False))
    # eager: keep the rank dim so the sharded global is [nranks, ...]
    out = _apply(
        Tensor(full),
        g,
        lambda x: per_shard(x, keep_rank_dim=True),
        in_specs=P(),
        out_specs=P(g.axis_name),
    )
    return _ret(tensor, out)


def all_to_all(out_tensor_list, in_tensor_list=None, group=None, sync_op=True):
    """reference ``collective.py:1844`` / ``global_scatter_op`` ≙
    lax.all_to_all. Ranks exchange the i-th slice of their list."""
    g = group or _default_group()
    if isinstance(out_tensor_list, (list,)) and in_tensor_list is None:
        raise ValueError("alltoall requires in_tensor_list")
    x = (
        jnp.stack([_unwrap(t) for t in in_tensor_list], axis=0)
        if isinstance(in_tensor_list, (list, tuple))
        else _unwrap(in_tensor_list)
    )

    def per_shard(s):
        return lax.all_to_all(s, g.axis_name, split_axis=0, concat_axis=0, tiled=False)

    if _in_spmd(g.axis_name):
        out = per_shard(x)
    else:
        out = _apply(
            Tensor(x), g, per_shard, in_specs=P(), out_specs=P(g.axis_name)
        )
    if isinstance(out_tensor_list, list):
        parts = [jnp.squeeze(p, 0) for p in jnp.split(out, out.shape[0], axis=0)]
        out_tensor_list.extend(Tensor(p) for p in parts)
        return out_tensor_list
    return Tensor(out)


alltoall = all_to_all


def alltoall_single(
    in_tensor,
    out_tensor=None,
    in_split_sizes=None,
    out_split_sizes=None,
    group=None,
    sync_op=True,
):
    """reference ``collective.py:1945`` ≙ lax.all_to_all tiled on dim0."""
    g = group or _default_group()
    x = _unwrap(in_tensor)

    def per_shard(s):
        return lax.all_to_all(s, g.axis_name, split_axis=0, concat_axis=0, tiled=True)

    if _in_spmd(g.axis_name):
        out = per_shard(x)
    else:
        # eager: shard dim0 = rank dim; exchange this rank's row pieces
        out = _apply(Tensor(x), g, lambda s: per_shard(s[0])[None])
    if out_tensor is not None:
        return _ret(out_tensor, out)
    return Tensor(out)


def _shift(tensor, group, offset):
    """ppermute by ``offset`` along the group ring (PP p2p primitive,
    ≙ send_v2/recv_v2 pairs ``operators/collective/send_v2_op.cc``)."""
    g = group or _default_group()
    n = g.nranks
    perm = [(i, (i + offset) % n) for i in range(n)]

    def per_shard(x):
        return lax.ppermute(x, g.axis_name, perm)

    if _in_spmd(g.axis_name):
        return per_shard(_unwrap(tensor))
    return _apply(tensor, g, per_shard)


# eager p2p channel: single-controller send/recv pairs execute sequentially
# in one process, so a FIFO per (group, dst rank) delivers the actual payload
# (the reference's socket/NCCL channel collapses to a queue); keying on the
# destination keeps interleaved sends to different destinations paired with
# the right recv
_P2P_CHANNEL: dict[tuple, list] = {}


def send(tensor, dst=0, group=None, sync_op=True):
    """Point-to-point send (reference ``collective.py:2033`` / send_v2).

    XLA has no true p2p; the two supported idioms are:
      * eager — the paired :func:`recv` in the same process pops the payload
        from a FIFO keyed on (group, dst) (single-controller: both ends live
        here);
      * spmd  — use :func:`recv` with a *relative* ``src`` offset (the
        uniform-ring pattern of PP schedules), or ``lax.ppermute`` directly
        for irregular patterns. ``send`` itself is a no-op in spmd: the
        movement is expressed by the receiving side's permute.
    """
    g = group or _default_group()
    if not _in_spmd(g.axis_name):
        _P2P_CHANNEL.setdefault((g.id, int(dst)), []).append(_unwrap(tensor))
    return tensor


def recv(tensor, src=0, group=None, sync_op=True):
    """Point-to-point receive (reference ``collective.py:2096`` / recv_v2).

    Eager: pops the payload queued by the paired :func:`send` whose ``dst``
    names this receiver (single-controller: the receiving "rank" is the
    group's current rank, 0 outside spmd). Spmd: ``src`` is the *relative*
    ring offset to receive from (``src=1`` ⇒ rank r gets rank r-1's value ≙
    ppermute shift by +1) — absolute-rank scattered p2p should use
    ``lax.ppermute`` directly.
    """
    g = group or _default_group()
    if _in_spmd(g.axis_name):
        return _ret(tensor, _shift(tensor, g, src))
    # single-controller pairing: when exactly one destination has pending
    # sends, play that rank (the classic send(dst=1); recv() simulation).
    # Multiple pending destinations are ambiguous — the receiver has no rank
    # identity in eager — so raise instead of misdelivering.
    pending = [k for k, v in _P2P_CHANNEL.items() if k[0] == g.id and v]
    if len(pending) > 1:
        raise RuntimeError(
            "recv() on group %d is ambiguous: pending sends to ranks %s — "
            "receive them in destination order or use spmd p2p"
            % (g.id, sorted(k[1] for k in pending))
        )
    if not pending:
        raise RuntimeError(
            "recv() without a pending send() on group %d (eager p2p pairs "
            "must be issued in order)" % g.id
        )
    return _ret(tensor, _P2P_CHANNEL[pending[0]].pop(0))


class _Task:
    """ProcessGroup::Task shim (reference ``ProcessGroup.h:55``): XLA
    dispatch is async already; wait() just blocks on the array."""

    def __init__(self, tensor):
        self._t = tensor

    def wait(self):
        v = self._t._value if isinstance(self._t, Tensor) else self._t
        if hasattr(v, "block_until_ready"):
            v.block_until_ready()
        return True

    def is_completed(self):
        return True


def isend(tensor, dst=0, group=None):
    send(tensor, dst, group)
    return _Task(tensor)


def irecv(tensor, src=0, group=None):
    recv(tensor, src, group)
    return _Task(tensor)


def barrier(group=None):
    """reference ``collective.py:308`` / ``barrier_op``. psum of a scalar
    forces a cross-device sync point."""
    g = group or _default_group()
    if _in_spmd(g.axis_name):
        lax.psum(jnp.ones(()), g.axis_name)
        return
    import jax as _jax

    if _jax.process_count() > 1:
        if g.nranks != _jax.process_count():
            raise NotImplementedError(
                "multi-controller barrier on a subgroup is not supported "
                "(sync_global_devices is global); barrier on the default "
                "group instead")
        from jax.experimental import multihost_utils as mhu

        mhu.sync_global_devices("paddle_tpu.distributed.barrier")
        return
    t = Tensor(jnp.ones((g.nranks,)))
    all_reduce(t, group=g)
    t._value.block_until_ready()


def wait(tensor, group=None, use_calc_stream=True):
    """reference ``collective.py wait`` / c_wait_* stream ops: XLA needs no
    stream fences; block on data readiness."""
    v = _unwrap(tensor)
    if hasattr(v, "block_until_ready"):
        v.block_until_ready()


def stream_sync():
    """c_sync_calc_stream / c_sync_comm_stream ≙ drain all device work."""
    jax.effects_barrier()
