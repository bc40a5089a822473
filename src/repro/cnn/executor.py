"""Graph executor: runs a CNN under a DYNAMAP ExecutionPlan.

The central Computing Unit analogy holds here too: every conv dispatches to
the same overlay (``overlay.apply_conv``), only the per-layer binding —
algorithm wrapper plus dataflow/(p1, p2) GEMM blocks — differs (algorithm
and dataflow switching, §3). Because all three algorithms compute the same
convolution, executing under *any* plan must produce identical outputs —
that invariant is what the integration tests assert.

Two execution modes:

* ``forward`` — eager: Python walks the graph per call, dispatching each
  layer. Convenient for experiments; slow under traffic.
* ``compile_plan`` — the plan-compilation pipeline: graph topology and the
  plan's per-layer algorithm/dataflow choices are lowered to a static
  spec (``core.mapper.lower_plan``) and closed over at trace time, yielding
  ONE ``jax.jit``-compiled program per (graph, plan) with no Python dispatch
  on the hot path. The compiled program is batched: it accepts ``(H, W, C)``
  or ``(B, H, W, C)`` inputs, so it can serve batched traffic directly
  (see ``serving.cnn_engine.CNNServingEngine``). With ``mesh=`` the batch
  dimension additionally shards across a device mesh's data axes
  (params replicated) — same lowered program, multi-chip placement.

Compiled programs never close over params (weights are call arguments), so
they are shareable across models: ``ExecutableCache`` +
``compile_plan(..., cache=)`` key each executable by ``(graph hash, plan,
bucket, mesh, options)`` and hand multi-tenant engines the same compiled
body for every tenant that shares an architecture (see
``serving.multi_engine``).
"""
from __future__ import annotations

import hashlib
import json
import threading
import warnings
from typing import Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.cnn import layers as L
from repro.cnn import overlay
from repro.core.algorithms import Algorithm, IM2COL
from repro.core.graph import Graph, LayerKind
from repro.core.layouts import LayoutSpec, is_nhwc
from repro.core.mapper import (ConvLowering, ExecutionPlan, LoweredProgram,
                               lower_plan, plan_fingerprint)
from repro.kernels.layouts import materialize, restore

Params = Dict[int, Dict[str, jax.Array]]
Lowering = Union[LoweredProgram, Dict[int, ConvLowering]]


# ---------------------------------------------------------------------------
# Shared executable cache (multi-tenant serving).
#
# Compiled programs close over (graph structure, plan, tuning winners,
# compile options) — params stay call arguments — so two *models* that share
# an architecture (same graph hash) can share every bucket executable even
# though their weights differ. ``ExecutableCache`` is that sharing, keyed by
# ``executable_cache_key``: (graph hash, plan fingerprint, bucket, mesh,
# remaining compile options). ``MultiModelEngine`` passes one cache to every
# tenant engine; the second tenant of an architecture compiles nothing.
# ---------------------------------------------------------------------------

def graph_hash(graph: Graph) -> str:
    """Stable structural hash of a CNN graph: layer kinds, conv signatures,
    non-conv attrs and edges — node *names* are display-only and excluded.
    Two independently built graphs with identical structure hash equal (the
    multi-tenant case: one architecture, many weight sets), and any
    structural difference — a channel count, a stride, an edge — changes
    the hash, so distinct models can never collide on a cache key."""
    h = hashlib.sha256()
    for nid in sorted(graph.nodes):
        node = graph.nodes[nid]
        c = node.conv
        conv_sig = ("-" if c is None else
                    f"{c.c_in}x{c.c_out}_{c.h1}x{c.h2}_{c.k1}x{c.k2}"
                    f"_s{c.stride}_{c.pad}")
        attrs = ";".join(f"{k}={node.attrs[k]!r}" for k in sorted(node.attrs))
        h.update(f"n{nid}|{node.kind.value}|{conv_sig}|{attrs}\n".encode())
    for src, dst in sorted(graph.edges):
        h.update(f"e{src}>{dst}\n".encode())
    return h.hexdigest()[:16]


# The plan's content fingerprint moved next to ExecutionPlan itself
# (core.mapper.plan_fingerprint) so the hot-swap supervisor can compare
# plans without importing the executor; the private alias survives for
# existing call sites.
_plan_fingerprint = plan_fingerprint


def _tuning_fingerprint(tuning) -> Optional[str]:
    """Content hash of a ``TuningRecord`` — records are keyed by conv
    signature, not by graph, so the same record object (or an equal reload
    of it) fingerprints equal and lets tenants share tuned executables."""
    if tuning is None:
        return None
    blob = json.dumps(tuning.to_json(), sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def _mesh_fingerprint(mesh):
    if mesh is None:
        return None
    return (tuple(str(a) for a in mesh.axis_names),
            tuple(int(s) for s in mesh.devices.shape),
            tuple(int(d.id) for d in mesh.devices.flat))


def executable_cache_key(graph: Graph, plan: Optional[ExecutionPlan] = None,
                         *, default_algo: Algorithm = IM2COL,
                         use_pallas: bool = False,
                         interpret: Optional[bool] = None,
                         epilogue: str = "relu",
                         tuning=None,
                         tuning_batch: Optional[int] = None,
                         avg_pool_via: str = "jnp",
                         elide: bool = True,
                         elide_overrides: Optional[Dict[Tuple[int, int],
                                                        bool]] = None,
                         mesh=None,
                         donate: bool = False,
                         act_scales: Optional[Dict[int, float]] = None
                         ) -> tuple:
    """The ``(graph hash, plan, bucket, mesh, options)`` identity of one
    compiled executable: everything ``compile_plan`` closes over EXCEPT
    params (call arguments — weights never key the cache) and
    ``fault_hook`` (a host-side wrapper applied outside the cache, so a
    fault-armed engine and a clean one still share the compiled body).
    The plan fingerprint carries per-layer precisions and ``act_scales``
    the calibrated activation scales, so an int8 plan and the bf16 plan of
    the same architecture can never collide on a key."""
    return (graph_hash(graph), _plan_fingerprint(plan), default_algo.key,
            bool(use_pallas), interpret, epilogue,
            _tuning_fingerprint(tuning), int(tuning_batch or 1),
            avg_pool_via, bool(elide),
            (None if elide_overrides is None
             else tuple(sorted(elide_overrides.items()))),
            _mesh_fingerprint(mesh), bool(donate),
            (None if act_scales is None
             else tuple(sorted((int(n), float(s))
                               for n, s in act_scales.items()))))


class ExecutableCache:
    """Process-wide cache of compiled overlay programs, shared across
    serving engines (the multi-tenant executable cache — ROADMAP's f-CNNx
    direction). ``get_or_compile`` returns the cached callable for a key or
    builds-and-stores it; hit/miss counters feed ``stats()`` and the
    ``bench_multi_model`` cross-model-reuse gate. Entries are never evicted
    — one entry per (architecture, plan, bucket, mesh, options) is exactly
    the working set a serving process needs resident.

    Thread-safe: the hot-swap supervisor compiles replacement bucket
    ladders on a background thread against the same cache the serving
    thread reads, so lookup-and-store runs under a lock (held across the
    build too — two threads racing on one key must not compile twice and
    publish different callables for it)."""

    def __init__(self) -> None:
        self._store: Dict[tuple, Callable] = {}
        self.hits = 0
        self.misses = 0
        self._lock = threading.RLock()

    def get_or_compile(self, key: tuple,
                       builder: Callable[[], Callable]) -> Callable:
        with self._lock:
            run = self._store.get(key)
            if run is not None:
                self.hits += 1
                return run
            self.misses += 1
            run = builder()
            self._store[key] = run
            return run

    def __len__(self) -> int:
        return len(self._store)

    def __contains__(self, key: tuple) -> bool:
        return key in self._store

    def stats(self) -> Dict[str, int]:
        return {"entries": len(self._store), "hits": self.hits,
                "misses": self.misses}


class _Staged:
    """One node's output as staged for its consumers: the value in the
    edge's store format plus a lazily-restored NHWC view (computed at most
    once per producer, shared by every mismatched consumer — the split
    vertex materializes ONE format and fans it out)."""

    __slots__ = ("value", "spec", "_nhwc")

    def __init__(self, value: jax.Array,
                 spec: Optional[LayoutSpec] = None) -> None:
        self.value = value
        self.spec = None if is_nhwc(spec) else spec
        self._nhwc = value if self.spec is None else None

    def nhwc(self) -> jax.Array:
        if self._nhwc is None:
            self._nhwc = restore(self.value, self.spec)   # converting load
        return self._nhwc

    def in_layout(self, spec: Optional[LayoutSpec]) -> jax.Array:
        """The value as a consumer's ``in_layout`` expects it."""
        if is_nhwc(spec):
            return self.nhwc()
        if self.spec == spec:
            return self.value                             # matched load
        return materialize(self.nhwc(), spec)


def init_params(graph: Graph, key: jax.Array,
                dtype=jnp.float32, conv_bias: bool = True) -> Params:
    """Per-layer parameter pytree. Convs get a zero-initialized per-channel
    bias (``conv_bias=False`` reproduces the bias-free PR-2 layout) which
    the ``bias``/``bias_relu`` fused epilogues consume — so GoogleNet /
    Inception lower CONV+bias+ReLU to ONE overlay call per layer."""
    params: Params = {}
    for nid in graph.topo_order():
        node = graph.nodes[nid]
        if node.kind is LayerKind.CONV:
            m = node.conv
            key, sub = jax.random.split(key)
            fan_in = m.k1 * m.k2 * m.c_in
            w = jax.random.normal(sub, (m.k1, m.k2, m.c_in, m.c_out),
                                  dtype) / jnp.sqrt(fan_in)
            params[nid] = {"w": w}
            if conv_bias:
                params[nid]["b"] = jnp.zeros((m.c_out,), dtype)
        elif node.kind is LayerKind.FC:
            key, sub = jax.random.split(key)
            fin = int(node.attrs["in_features"])
            fout = int(node.attrs["out_features"])
            params[nid] = {
                "w": jax.random.normal(sub, (fin, fout), dtype) / jnp.sqrt(fin),
                "b": jnp.zeros((fout,), dtype),
            }
    return params


def _eval_graph(graph: Graph, lowering: Lowering,
                params: Params, x: jax.Array,
                use_pallas: bool, interpret: Optional[bool],
                avg_pool_via: str = "jnp",
                conv_tap: Optional[Callable[[int, jax.Array], None]] = None
                ) -> jax.Array:
    """Walk the graph once; with ``x`` a tracer this IS the trace that
    ``compile_plan`` stages out — all dict lookups and dispatch below happen
    at trace time only.

    Inter-layer values travel in the store formats the ``LoweredProgram``
    realized from ``plan.store_formats``: a producer stages its edge's
    format once (conv layers fuse the conversion via ``out_layout``,
    non-conv producers materialize it here), matched consumers read it
    directly (``in_layout``), and mismatched consumers restore to NHWC —
    the Table 2 converting load. A plain ``{nid: ConvLowering}`` dict (no
    transitions) reproduces the layout-agnostic walk.

    ``conv_tap`` (calibration hook) is called with ``(nid, nhwc_input)``
    for every conv node — ``core.quant.calibrate_act_scales`` uses it to
    observe per-layer activation ranges on an eager f32 walk."""
    batched = x.ndim == 4
    store_specs: Dict[int, LayoutSpec] = getattr(lowering, "store_specs", {})
    values: Dict[int, _Staged] = {}

    def _stage(nid: int, y: jax.Array) -> None:
        """Stage a non-conv producer's NHWC output in its edge's format."""
        spec = store_specs.get(nid)
        values[nid] = _Staged(materialize(y, spec), spec)

    for nid in graph.topo_order():
        node = graph.nodes[nid]
        preds = graph.predecessors(nid)
        if node.kind is LayerKind.INPUT:
            _stage(nid, x)
            continue
        if node.kind is LayerKind.CONV:
            low = lowering[nid]
            m = node.conv
            pad = "SAME" if m.pad == "same" else "VALID"
            epi = low.epilogue
            bias = params[nid].get("b") if epi.startswith("bias") else None
            if epi.startswith("bias") and bias is None:
                # Bias-free legacy params under a bias-carrying lowering:
                # degrade to the bias-less epilogue (conv math unchanged).
                epi = "relu" if epi.endswith("relu") else "none"
            in_layout = getattr(low, "in_layout", None)
            out_layout = getattr(low, "out_layout", None)
            if conv_tap is not None:
                conv_tap(nid, values[preds[0]].nhwc())
            xin = values[preds[0]].in_layout(in_layout)
            y = overlay.apply_conv(xin, params[nid]["w"], low.algo,
                                   low.dataflow, low.p1, low.p2,
                                   stride=m.stride, padding=pad,
                                   use_pallas=use_pallas,
                                   backend=(None if low.backend == "auto"
                                            else low.backend),
                                   interpret=interpret,
                                   epilogue=epi, bias=bias,
                                   in_layout=in_layout,
                                   out_layout=out_layout,
                                   precision=getattr(low, "precision",
                                                     "bf16"),
                                   in_scale=getattr(low, "in_scale", None),
                                   out_scale=getattr(low, "out_scale", None),
                                   in_quantized=getattr(low, "in_quantized",
                                                        False))
            if not epi.endswith("relu"):
                # The graph semantics are CONV→ReLU; a relu-carrying
                # epilogue already ran it inside the overlay call — ONE
                # call, fused. ReLU commutes with the (linear-gather)
                # store formats, so an unfused ReLU applies to the staged
                # value directly.
                y = L.relu(y)
            values[nid] = _Staged(y, out_layout)
            continue
        # Non-conv consumers read the 3-D tensor; restored here only when
        # a predecessor staged a non-NHWC format (the converting load) —
        # conv consumers above never touch this view.
        ins = [values[p].nhwc() for p in preds]
        if node.kind is LayerKind.POOL_MAX:
            pad = "SAME" if node.attrs.get("pad", "same") == "same" else "VALID"
            y = L.max_pool(ins[0], int(node.attrs["k"]),
                           int(node.attrs["stride"]), pad)
        elif node.kind is LayerKind.POOL_AVG:
            pad = "SAME" if node.attrs.get("pad", "same") == "same" else "VALID"
            y = L.avg_pool(ins[0], int(node.attrs["k"]),
                           int(node.attrs["stride"]), pad,
                           via=avg_pool_via,
                           use_pallas=use_pallas,
                           interpret=interpret)
        elif node.kind is LayerKind.CONCAT:
            y = jnp.concatenate(ins, axis=-1)
        elif node.kind is LayerKind.ADD:
            y = L.relu(sum(ins))
        elif node.kind is LayerKind.GLOBAL_POOL:
            gap = L.global_avg_pool(ins[0])          # (C,) or (B, C)
            y = (gap[:, None, None, :] if batched
                 else gap[None, None, :])
        elif node.kind is LayerKind.FC:
            flat = (ins[0].reshape(ins[0].shape[0], -1) if batched
                    else ins[0].reshape(-1))
            y = L.fc(flat, params[nid]["w"], params[nid]["b"])
        elif node.kind is LayerKind.SOFTMAX:
            y = jax.nn.softmax(ins[0])
        elif node.kind is LayerKind.OUTPUT:
            y = ins[0]
        else:
            raise ValueError(f"unhandled node kind {node.kind}")
        _stage(nid, y)
    return values[graph.sink()].nhwc()


def forward(graph: Graph, params: Params,
            x: jax.Array, plan: Optional[ExecutionPlan] = None,
            default_algo: Algorithm = IM2COL,
            use_pallas: bool = False,
            interpret: Optional[bool] = None,
            epilogue: str = "relu",
            tuning=None,
            tuning_batch: Optional[int] = None,
            elide: bool = True,
            elide_overrides: Optional[Dict[Tuple[int, int], bool]] = None,
            act_scales: Optional[Dict[int, float]] = None,
            conv_tap: Optional[Callable[[int, jax.Array], None]] = None
            ) -> jax.Array:
    """Eager inference. ``x``: (H, W, C) single image (the paper's no-batch
    low-latency setting) or (B, H, W, C) batch. Each call re-interprets the
    plan in Python — use ``compile_plan`` for the dispatch-free hot path.
    ``act_scales`` supplies calibrated activation scales for int8 layers;
    ``conv_tap(nid, nhwc_input)`` observes every conv input (calibration)."""
    lowering = lower_plan(graph, plan, default_algo,
                          epilogue=epilogue, tuning=tuning,
                          batch=tuning_batch, elide=elide,
                          elide_overrides=elide_overrides,
                          act_scales=act_scales)
    return _eval_graph(graph, lowering, params, x, use_pallas, interpret,
                       conv_tap=conv_tap)


def compile_plan(graph: Graph, plan: Optional[ExecutionPlan] = None,
                 default_algo: Algorithm = IM2COL,
                 use_pallas: bool = False,
                 interpret: Optional[bool] = None,
                 epilogue: str = "relu",
                 tuning=None,
                 tuning_batch: Optional[int] = None,
                 avg_pool_via: str = "jnp",
                 elide: bool = True,
                 elide_overrides: Optional[Dict[Tuple[int, int], bool]] = None,
                 mesh=None,
                 donate: bool = False,
                 fault_hook: Optional[Callable[[], None]] = None,
                 cache: Optional[ExecutableCache] = None,
                 act_scales: Optional[Dict[int, float]] = None,
                 ) -> Callable[[Params, jax.Array], jax.Array]:
    """Lower (graph, plan) into one jit-compiled overlay program.

    Returns ``run(params, x) -> logits`` with ``x``: (H, W, C) or
    (B, H, W, C). The graph topology, every per-layer algorithm and
    dataflow/(p1, p2) block binding, AND every edge's DRAM store format
    (``plan.store_formats``, realized as ``LayoutTransition`` specs) are
    resolved *now* into a static ``LoweredProgram`` and closed over, so the
    traced program contains no Python dispatch; XLA sees the whole network
    and can fuse across layers. With ``elide=True`` (default) consumers
    read matching store formats directly — back-to-back Winograd layers
    stay in the scattered tile domain, im2col chains reuse the Toeplitz
    buffer — and the NHWC round trip survives only where layouts disagree;
    ``elide=False`` compiles the layout-agnostic always-round-trip baseline
    (kept for benchmarking); ``elide_overrides`` flips individual edges.
    One compilation is cached per input shape/dtype (batch sizes compile
    once each — pad to a fixed batch to avoid recompilation, as
    ``CNNServingEngine`` does).

    ``epilogue="relu"`` (the default) fuses each CONV's trailing ReLU into
    its overlay call; ``epilogue="none"`` reproduces the PR-1 unfused
    conv-then-relu lowering (kept for benchmarking). A ``tuning`` record
    from ``core.autotune`` replaces cost-model bindings with measured
    winners, including per-layer pallas/reference backend selection inside
    this single compiled program; ``tuning_batch`` picks the batch bucket
    whose measured winners bind this executable (None → bucket 1), so a
    bucketed serving engine compiles one program per bucket, each under the
    bindings measured at that batch size. ``avg_pool_via="overlay"`` routes
    AvgPool layers through the overlay's GEMM unit (§3.4) instead of the
    jnp reduce-window.

    ``mesh`` (a ``jax.sharding.Mesh``) turns on data-parallel multi-chip
    execution: the batch dimension of ``x`` is placed on the mesh's data
    axes (``distributed.sharding.data_axes`` — a ``("data",)`` mesh from
    ``launch.mesh.make_data_mesh`` in the common case) via
    ``NamedSharding``/``PartitionSpec`` and params are replicated, so every
    chip runs the SAME lowered overlay program on its batch shard — the
    algorithm/layout mapping is untouched; only placement changes, which is
    why sharding composes with tuning, epilogues and layout elision for
    free. Data-parallel conv inference needs no collectives, so scaling is
    communication-free up to the output gather. The returned callable then
    requires batched ``(B, H, W, C)`` input with ``B`` divisible by the
    data-shard count (jit rejects uneven input partitions); callers keeping
    params on-device should pre-place them replicated (as
    ``CNNServingEngine`` does) so the hot path never re-transfers them.

    ``donate=True`` threads ``jax.jit(..., donate_argnums=)`` for the
    batched input ``x``: XLA may reuse its device buffer for outputs and
    intermediates, so a serving loop that re-stages every tick from host
    memory (as the pipelined ``CNNServingEngine`` does) holds a constant
    device footprint across ticks instead of one live input buffer per
    in-flight dispatch. The donated argument is consumed by the call —
    never pass a ``jax.Array`` you still need afterwards (host numpy
    staging buffers are safe: the transfer makes a fresh device copy, and
    only that copy is donated). Donation composes with ``mesh=``: the
    input's ``NamedSharding`` pins placement, donation only allows
    aliasing of the per-chip buffers.

    ``fault_hook`` (robustness testing) is a zero-arg callable invoked on
    the host before EVERY invocation of the compiled program — never at
    compile time, never inside the traced computation. It may raise (an
    injected dispatch failure, e.g. ``distributed.fault.DeviceFault``) or
    sleep (a straggling launch path); the math is untouched, so a hooked
    executable's outputs stay bitwise identical to an unhooked one.
    ``CNNServingEngine(fault_plan=...)`` threads its per-tick fault
    schedule through this hook and wraps the call in a bounded
    retry-with-backoff loop; ``fault_hook=None`` (default) adds no
    wrapper at all.

    ``cache`` (an ``ExecutableCache``) makes compilation shared: the call
    first looks up ``executable_cache_key(...)`` — (graph hash, plan
    fingerprint, bucket, mesh, compile options; params and ``fault_hook``
    excluded) — and only compiles on a miss. Two models with the same
    architecture (equal ``graph_hash``) under the same plan/tuning/options
    therefore share ONE compiled program per bucket; the fault hook is
    wrapped *around* the cached body, so fault-armed and clean engines
    share too. ``cache=None`` (default) compiles unconditionally.

    ``act_scales`` ({conv node id: activation scale}, from
    ``core.quant.calibrate_act_scales``) feeds the plan's int8 layers their
    calibrated per-tensor input scales; it enters the cache key, so plans
    differing only in calibration compile separately. A plan with no int8
    layers ignores it.
    """
    if cache is not None:
        key = executable_cache_key(
            graph, plan, default_algo=default_algo, use_pallas=use_pallas,
            interpret=interpret, epilogue=epilogue, tuning=tuning,
            tuning_batch=tuning_batch, avg_pool_via=avg_pool_via,
            elide=elide, elide_overrides=elide_overrides, mesh=mesh,
            donate=donate, act_scales=act_scales)
        base = cache.get_or_compile(key, lambda: _compile_plan_base(
            graph, plan, default_algo=default_algo, use_pallas=use_pallas,
            interpret=interpret, epilogue=epilogue, tuning=tuning,
            tuning_batch=tuning_batch, avg_pool_via=avg_pool_via,
            elide=elide, elide_overrides=elide_overrides, mesh=mesh,
            donate=donate, act_scales=act_scales))
        return _with_fault_hook(base, fault_hook)
    return _with_fault_hook(
        _compile_plan_base(graph, plan, default_algo=default_algo,
                           use_pallas=use_pallas, interpret=interpret,
                           epilogue=epilogue, tuning=tuning,
                           tuning_batch=tuning_batch,
                           avg_pool_via=avg_pool_via, elide=elide,
                           elide_overrides=elide_overrides, mesh=mesh,
                           donate=donate, act_scales=act_scales),
        fault_hook)


def _compile_plan_base(graph: Graph, plan: Optional[ExecutionPlan], *,
                       default_algo: Algorithm, use_pallas: bool,
                       interpret: Optional[bool], epilogue: str,
                       tuning, tuning_batch: Optional[int],
                       avg_pool_via: str, elide: bool,
                       elide_overrides: Optional[Dict[Tuple[int, int], bool]],
                       mesh, donate: bool,
                       act_scales: Optional[Dict[int, float]] = None
                       ) -> Callable[[Params, jax.Array], jax.Array]:
    """The hookless compile body ``compile_plan`` caches: lower, trace,
    jit, (optionally) shard — everything except the per-engine fault-hook
    wrapper, which must never be shared between engines."""
    lowering = lower_plan(graph, plan, default_algo,
                          epilogue=epilogue, tuning=tuning,
                          batch=tuning_batch, elide=elide,
                          elide_overrides=elide_overrides,
                          act_scales=act_scales)
    donate_argnums = (1,) if donate else ()

    def _run(params: Params, x: jax.Array) -> jax.Array:
        return _eval_graph(graph, lowering, params, x, use_pallas, interpret,
                           avg_pool_via)

    if mesh is None:
        return _quiet_donation(jax.jit(_run, donate_argnums=donate_argnums),
                               donate)

    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import (batch_input_sharding,
                                            data_shard_count, replicated)
    n_shards = data_shard_count(mesh)
    x_sharding = batch_input_sharding(mesh)
    # Each chip runs the whole overlay program on its own batch shard. The
    # SPMD partitioner cannot split a Pallas kernel, so without shard_map
    # it would gather the batch and run every kernel whole on every chip.
    per_chip = jax.shard_map(_run, mesh=mesh,
                             in_specs=(replicated(mesh).spec,
                                       x_sharding.spec),
                             out_specs=P(x_sharding.spec[0]),
                             check_vma=False)
    jitted = jax.jit(per_chip, in_shardings=(replicated(mesh), x_sharding),
                     donate_argnums=donate_argnums)
    lower = jitted.lower
    jitted = _quiet_donation(jitted, donate)

    def run(params: Params, x: jax.Array) -> jax.Array:
        if x.ndim != 4:
            raise ValueError(
                "mesh-sharded compiled plans take batched (B, H, W, C) "
                f"input; got shape {tuple(x.shape)}")
        if x.shape[0] % n_shards:
            raise ValueError(
                f"batch {x.shape[0]} does not divide across "
                f"{n_shards} data shards — pad to a multiple (the serving "
                "engine's sharded bucket ladder guarantees this)")
        return jitted(params, x)

    run.mesh = mesh
    run.data_shards = n_shards
    run.lower = lower                 # AOT inspection, as on the plain jit
    return run


def _with_fault_hook(run: Callable, fault_hook: Optional[Callable[[], None]]
                     ) -> Callable:
    """Outermost wrapper: call ``fault_hook()`` before each invocation so
    injected dispatch faults/delays surface exactly where a real device
    error would — at the call, after any mesh validation wrapper built
    the arguments. No hook, no wrapper (the common path stays
    unchanged)."""
    if fault_hook is None:
        return run

    def hooked(params: Params, x: jax.Array) -> jax.Array:
        fault_hook()
        return run(params, x)

    for attr in ("mesh", "data_shards"):
        if hasattr(run, attr):
            setattr(hooked, attr, getattr(run, attr))
    return hooked


def _quiet_donation(jitted: Callable, donate: bool) -> Callable:
    """Donation is an *allowance*: when no output of the program can alias
    the donated input (a CNN's logits never match the image shape), XLA
    ignores it and jax emits an advisory UserWarning at compile time.
    That is the expected outcome on such programs — donation still pays
    off wherever an intermediate or output CAN take the buffer (and on
    runtimes that reuse donated space for temporaries) — so the advisory
    is suppressed for donated executables rather than logged once per
    bucket compile."""
    if not donate:
        return jitted

    def run(params: Params, x: jax.Array) -> jax.Array:
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return jitted(params, x)

    return run
