"""Persistent executable store: compiled XLA programs as durable artifacts
(ROADMAP item 5 — kill recompilation across process and host lifetimes).

Recompilation is the dominant cost in three hot recovery/scale paths:
elastic MTTR (the survivor-layout pod-generation recompile), serving
replica spin-up under the autoscaler (the decode-chunk + per-bucket
prefill programs), and evolutionary layout search (every candidate plan
pays a full compile). The Podracer/Anakin lineage already enforces
compile-ONCE within a process; this module extends the discipline across
process and host lifetimes by making the compiled program itself a
store entry:

- :class:`ExecutableStore` — an on-disk registry layered on the shared
  commit-dir protocol (:mod:`agilerl_tpu.resilience.store`): every entry is
  atomically published, sha-validated on read, torn entries are skipped and
  counted (never loaded), and GC keeps the newest entry per fingerprint.
- :func:`fingerprint_parts` / :func:`fingerprint_digest` — the strict cache
  key: step name + resolved-plan hash + abstract arg signature
  (shapes / dtypes / shardings) + donate_argnums + jax/jaxlib/libtpu
  versions + backend platform + device topology, PLUS a sha256 of the
  lowered HLO (so two steps with identical metadata but different step
  maths — e.g. a different learning rate baked into a closure — can never
  collide). Any mismatch is a MISS, never a wrong executable.
- :func:`load_or_compile` — lower once, then either deserialize the stored
  executable (``jax.experimental.serialize_executable``) or compile and
  republish. A deserialization failure (version drift the fingerprint
  missed, foreign-host artifact) falls back to compile-and-republish with
  a warn-once and a ``compile_cache/deserialize_failures_total`` count.
- :class:`CachedFunction` — a drop-in wrapper over a jitted callable that
  performs load-or-compile per call signature (what the elastic
  controller, the serving tier and ``EvolvableAlgorithm.jit_fn`` wire in).

Everything is CPU-backend testable: serialize → deserialize → call on the
virtual CPU mesh is bit-identical to the fresh compile (tier-1 gated), and
the warm path triggers ZERO backend-compile events (CompileGuard-proven).

Opt-in: pass ``cache=``/``compile_cache=`` at the consumer, or set
``AGILERL_TPU_COMPILE_CACHE=/path/to/store`` to switch every wired
consumer on at once. Warm-vs-cold is visible in the telemetry plane via
``compile_cache/{hits,misses}_total``, ``compile_cache/{load_s,compile_s}``
histograms and ``compile_cache.load`` / ``compile_cache.compile`` trace
spans.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from agilerl_tpu.resilience.store import (
    CommitDirStore,
    committed_entries,
    entry_seq,
)

#: environment opt-in: a store directory every wired consumer resolves when
#: no explicit ``cache=`` / ``compile_cache=`` argument is given
CACHE_ENV = "AGILERL_TPU_COMPILE_CACHE"

#: wall-time buckets for the load/compile histograms — loads are tens of ms
#: to seconds, compiles seconds to minutes (the 7B GSPMD targets)
CACHE_TIME_BUCKETS = (0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.0, 5.0, 10.0,
                     30.0, 60.0, 120.0, 300.0, 600.0)

_FP_PREFIX = "fp_"
_ENTRY_PREFIX = "exe_"


# --------------------------------------------------------------------------- #
# JAX's own persistent compilation cache — one placement rule
# --------------------------------------------------------------------------- #


def enable_jax_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache for an entry point (a
    training script; the benchmark's harness has its own copy of the rule)
    and return the directory set in code.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself: nothing
    is set here and ``None`` comes back. Otherwise the cache lives at the
    fixed ``<checkout>/.jax_cache``. The path is part of the cache's key,
    so it is never derived from a temp name, a pid or the time."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip():
        return None
    # the checkout: the directory that holds the agilerl_tpu package
    path = str(Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# --------------------------------------------------------------------------- #
# Fingerprint — the strict cache key
# --------------------------------------------------------------------------- #


def _sha256_text(text: str) -> str:
    return hashlib.sha256(text.encode("utf-8")).hexdigest()


def runtime_versions() -> Dict[str, Optional[str]]:
    """jax / jaxlib / libtpu versions — compiled artifacts are only valid
    for the exact toolchain that produced them."""
    import jaxlib

    libtpu = None
    try:  # in-image pip package; absent on CPU-only deployments
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:
        libtpu = None
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}


def _sharding_desc(leaf: Any) -> Any:
    """JSON-able description of a leaf's sharding. NamedShardings record
    spec + mesh axes/sizes (device IDs are deliberately excluded — the
    topology component covers count/kind; a same-shaped mesh on the
    surviving hosts after recovery must HIT). Host numpy / python scalars,
    plain ShapeDtypeStructs and single-device arrays all normalise to
    ``"host"`` — they lower to the same program, and the equivalence is
    what lets ``warm_start`` prepare with abstract args and the runtime
    call with concrete ones resolve to ONE fingerprint."""
    sharding = getattr(leaf, "sharding", None)
    if sharding is None:
        return "host"
    from jax.sharding import NamedSharding, SingleDeviceSharding

    if isinstance(sharding, NamedSharding):
        return {
            "spec": [list(e) if isinstance(e, (tuple, list)) else e
                     for e in sharding.spec],
            "mesh": dict(sharding.mesh.shape),
        }
    if isinstance(sharding, SingleDeviceSharding):
        return "host"
    return type(sharding).__name__


def abstract_signature(args: Sequence[Any],
                       kwargs: Optional[Dict[str, Any]] = None) -> List[Any]:
    """Flat, JSON-able (path, shape, dtype, sharding) description of a call
    signature. Accepts concrete arrays, numpy, python scalars and
    ``ShapeDtypeStruct`` trees alike — everything the jit tracer would
    specialize on, minus the values."""
    sig: List[Any] = []
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        (tuple(args), dict(kwargs or {})))
    for path, leaf in flat:
        shape = getattr(leaf, "shape", None)
        if shape is None:
            shape = np.shape(leaf)
        dtype = getattr(leaf, "dtype", None)
        sig.append({
            "path": jax.tree_util.keystr(path),
            "shape": list(map(int, shape)),
            "dtype": str(dtype) if dtype is not None else type(leaf).__name__,
            "sharding": _sharding_desc(leaf),
        })
    sig.append({"treedef": str(treedef)})
    return sig


def plan_digest(plan: Any) -> Optional[str]:
    """sha256 over the plan's full resolved declaration (axes, every rule
    group, activation cut-points, dcn, strict) — TWO plans with one name
    but different rules can never share executables."""
    if plan is None:
        return None
    return _sha256_text(
        json.dumps(plan.to_dict(), sort_keys=True, default=str))


def topology_desc(mesh: Any = None,
                  devices: Optional[Sequence[Any]] = None) -> Dict[str, Any]:
    """Backend platform + device kind + count (+ mesh axes when given) —
    an executable is only valid on the topology it was compiled for."""
    if devices is None:
        if mesh is not None:
            devices = list(mesh.devices.flat)
        else:
            devices = jax.devices()
    devices = list(devices)
    d0 = devices[0]
    desc: Dict[str, Any] = {
        "platform": str(getattr(d0, "platform", jax.default_backend())),
        "device_kind": str(getattr(d0, "device_kind", "unknown")),
        "n_devices": len(devices),
    }
    if desc["platform"] == "cpu":
        # CPU executables are host-CLASS artifacts: XLA:CPU bakes in ISA
        # features the PJRT client does not expose, so the architecture is
        # the strongest key available — a store shared across unlike hosts
        # must live on per-host paths (docs/compile_cache.md)
        import platform as _platform

        desc["machine"] = _platform.machine()
    if mesh is not None:
        desc["mesh"] = dict(mesh.shape)
    return desc


def fingerprint_parts(
    name: str,
    *,
    args: Sequence[Any] = (),
    kwargs: Optional[Dict[str, Any]] = None,
    plan: Any = None,
    mesh: Any = None,
    devices: Optional[Sequence[Any]] = None,
    in_groups: Optional[Sequence[Optional[str]]] = None,
    donate_argnums: Sequence[int] = (),
    static_args: Optional[Dict[str, Any]] = None,
    extra: Any = None,
    lowered_sha256: Optional[str] = None,
    versions: Optional[Dict[str, Optional[str]]] = None,
) -> Dict[str, Any]:
    """The full fingerprint record (also written into the entry manifest so
    provenance is inspectable without unpickling). Every component the ISSUE
    contract names is a key: skew in ANY of them changes the digest."""
    return {
        "name": str(name),
        "plan": getattr(plan, "name", None),
        "plan_sha256": plan_digest(plan),
        "in_groups": list(in_groups) if in_groups is not None else None,
        "signature": abstract_signature(args, kwargs),
        "donate_argnums": sorted(map(int, donate_argnums)),
        "static_args": {k: repr(v) for k, v in (static_args or {}).items()},
        "versions": dict(versions if versions is not None
                         else runtime_versions()),
        "topology": topology_desc(mesh, devices),
        "lowered_sha256": lowered_sha256,
        "extra": extra,
    }


def fingerprint_digest(parts: Dict[str, Any]) -> str:
    return _sha256_text(json.dumps(parts, sort_keys=True, default=str))


# --------------------------------------------------------------------------- #
# The store
# --------------------------------------------------------------------------- #


class ExecutableStore:
    """On-disk executable registry over the shared commit-dir protocol.

    Layout: one ``fp_<digest>/`` directory per fingerprint, holding
    ``exe_<seq>`` commit-dir entries (payload = the serialized executable
    triple; manifest = fingerprint parts + compile provenance, readable
    without unpickling). Publishing GCs all but the newest ``keep_last``
    entries of THAT fingerprint — entries of one fingerprint are
    interchangeable by construction, so newest-wins; other fingerprints
    are never touched.

    Reads inherit the skip-torn contract verbatim from
    :class:`~agilerl_tpu.resilience.store.CommitDirStore`: a torn entry is
    counted (``compile_cache/torn_entries_total``), warned once, and the
    walk falls back to the next-newest entry — a torn store can cost a
    recompile, never a wrong program.
    """

    def __init__(
        self,
        directory: Union[str, Path],
        *,
        keep_last: int = 1,
        metrics=None,
        tracer=None,
    ):
        from agilerl_tpu import observability

        self.directory = Path(directory)
        self.directory.mkdir(parents=True, exist_ok=True)
        self.keep_last = int(keep_last)
        self.metrics = (metrics if metrics is not None
                        else observability.get_registry())
        self._tracer = tracer
        self._stores: Dict[str, CommitDirStore] = {}

    @property
    def tracer(self):
        if self._tracer is not None:
            return self._tracer
        from agilerl_tpu.observability import get_tracer

        return get_tracer()

    # -- per-fingerprint entry stores ------------------------------------- #
    def _entry_store(self, digest: str) -> CommitDirStore:
        store = self._stores.get(digest)
        if store is None:
            store = CommitDirStore(
                self.directory / f"{_FP_PREFIX}{digest}",
                prefix=_ENTRY_PREFIX,
                keep_last=self.keep_last,
                torn_counter="compile_cache/torn_entries_total",
                torn_help="compile-cache entries skipped as torn/corrupt",
                warn_prefix="compile-cache-torn",
                metrics=self.metrics,
                tracer=self._tracer,
            )
            self._stores[digest] = store
        return store

    def fingerprints(self) -> List[str]:
        """Digests with at least one committed entry."""
        out = []
        for d in sorted(self.directory.iterdir()):
            if d.is_dir() and d.name.startswith(_FP_PREFIX):
                if committed_entries(d, _ENTRY_PREFIX):
                    out.append(d.name[len(_FP_PREFIX):])
        return out

    def has(self, digest: str) -> bool:
        return bool(committed_entries(
            self.directory / f"{_FP_PREFIX}{digest}", _ENTRY_PREFIX))

    def get_payload(self, digest: str) -> Optional[Dict[str, Any]]:
        """Newest-first sha-validated walk of the fingerprint's entries;
        torn entries are skipped (counted + warned) and the walk falls back.
        None == MISS (no loadable entry)."""
        store = self._entry_store(digest)
        for entry in reversed(store.entries()):
            payload = store.load(entry)
            if payload is not None:
                return payload
        return None

    def read_manifest(self, digest: str) -> Optional[Dict[str, Any]]:
        """Newest loadable entry's manifest (provenance without unpickling);
        None when the fingerprint has no committed entries."""
        from agilerl_tpu.resilience.atomic import CorruptSnapshotError
        from agilerl_tpu.resilience.store import read_manifest

        entries = committed_entries(
            self.directory / f"{_FP_PREFIX}{digest}", _ENTRY_PREFIX)
        for entry in reversed(entries):
            try:
                return read_manifest(entry)
            except CorruptSnapshotError:
                continue
        return None

    def publish(self, digest: str, payload: Dict[str, Any],
                manifest_extra: Optional[Dict[str, Any]] = None) -> Path:
        """Atomically publish one executable under its fingerprint, then GC
        down to the newest ``keep_last`` entries of that fingerprint. The
        entry name embeds the pid BEFORE the ordering integer (the trailing
        int stays the sequence): two processes racing the same fingerprint
        miss stage under DIFFERENT names, so neither can rmtree the other's
        in-flight ``*.tmp`` staging dir or collide on the final rename —
        same-fingerprint entries are interchangeable, newest-seq wins."""
        store = self._entry_store(digest)
        seqs = [entry_seq(e.name) for e in store.entries()]
        seq = max([s for s in seqs if s is not None], default=-1) + 1
        return store.publish(f"{_ENTRY_PREFIX}{os.getpid()}_{seq:08d}",
                             payload, manifest_extra=manifest_extra)


def resolve_cache(cache: Any = None, *, metrics=None,
                  tracer=None) -> Optional[ExecutableStore]:
    """Normalise the ``cache=`` / ``compile_cache=`` argument every consumer
    accepts: an :class:`ExecutableStore` passes through, a str/Path builds a
    store bound to the CONSUMER's registry (per-replica metrics over one
    shared directory), ``None`` consults ``AGILERL_TPU_COMPILE_CACHE`` (the
    global opt-in), and ``False`` is explicitly off even when the env var
    is set."""
    if cache is False:
        return None
    if cache is None:
        env = os.environ.get(CACHE_ENV, "").strip()
        if not env:
            return None
        cache = env
    if isinstance(cache, ExecutableStore):
        return cache
    return ExecutableStore(cache, metrics=metrics, tracer=tracer)


# --------------------------------------------------------------------------- #
# load-or-compile
# --------------------------------------------------------------------------- #


def _metrics_of(store: Optional[ExecutableStore], metrics):
    if metrics is not None:
        return metrics
    if store is not None:
        return store.metrics
    from agilerl_tpu import observability

    return observability.get_registry()


def _tracer_of(store: Optional[ExecutableStore], tracer):
    if tracer is not None:
        return tracer
    if store is not None:
        return store.tracer
    from agilerl_tpu.observability import get_tracer

    return get_tracer()


def serialize_compiled(compiled) -> Dict[str, Any]:
    """The store payload for one ``jax.stages.Compiled``: the serialized
    executable bytes plus the in/out treedefs ``deserialize_and_load``
    needs (`jax.experimental.serialize_executable` triple), and the ids of
    the devices it was compiled for, in the order of its device
    assignment."""
    from jax.experimental import serialize_executable as se

    exe, in_tree, out_tree = se.serialize(compiled)
    devices = compiled.runtime_executable().local_devices()
    return {"exe": exe, "in_tree": in_tree, "out_tree": out_tree,
            "device_ids": [d.id for d in devices]}


def deserialize_payload(payload: Dict[str, Any]):
    """Load a stored executable onto the devices it was compiled for.
    ``deserialize_and_load`` would otherwise load it onto ALL of the
    backend's devices, and a one-device program then refuses its arguments
    in any process that sees more than one. An id this process does not
    have (or an entry stored without ids) raises: a miss to the caller."""
    from jax.experimental import serialize_executable as se

    by_id = {d.id: d for d in jax.devices()}
    return se.deserialize_and_load(
        payload["exe"], payload["in_tree"], payload["out_tree"],
        execution_devices=[by_id[i] for i in payload["device_ids"]])


def load_or_compile(
    jit_fn: Callable,
    args: Sequence[Any],
    kwargs: Optional[Dict[str, Any]] = None,
    *,
    name: str,
    store: Optional[ExecutableStore],
    plan: Any = None,
    mesh: Any = None,
    in_groups: Optional[Sequence[Optional[str]]] = None,
    donate_argnums: Sequence[int] = (),
    static_args: Optional[Dict[str, Any]] = None,
    extra: Any = None,
    metrics=None,
    tracer=None,
    compile_on_miss: bool = True,
) -> Tuple[Any, Dict[str, Any]]:
    """Lower ``jit_fn`` for ``args``/``kwargs``, then LOAD the matching
    stored executable or COMPILE and republish. Returns ``(compiled,
    info)`` where ``compiled`` is a callable ``jax.stages.Compiled``
    (call with the dynamic args only — baked static kwargs are dropped)
    and ``info`` records hit/miss, the fingerprint digest and timings.

    The fingerprint includes a sha256 of the lowered HLO on top of the
    metadata contract: lowering is cheap relative to backend compile and
    guarantees a closure-level semantic change (a different learning rate,
    a different loss flag) can never resolve to a stale executable. With
    ``store=None`` this degrades to plain AOT compile (no registry I/O).

    A stored entry that fails to DESERIALIZE (toolchain drift the
    fingerprint missed, artifact from an incompatible host) is never
    fatal: warn once, count ``compile_cache/deserialize_failures_total``,
    fall back to compile-and-republish.
    """
    metrics = _metrics_of(store, metrics)
    tracer = _tracer_of(store, tracer)
    t0 = time.perf_counter()
    lowered = jit_fn.lower(*args, **(kwargs or {}))
    lower_s = time.perf_counter() - t0
    parts = fingerprint_parts(
        name, args=args, kwargs=kwargs, plan=plan, mesh=mesh,
        in_groups=in_groups, donate_argnums=donate_argnums,
        static_args=static_args, extra=extra,
        lowered_sha256=_sha256_text(lowered.as_text()),
    )
    digest = fingerprint_digest(parts)
    info: Dict[str, Any] = {"fingerprint": digest, "name": name,
                            "lower_s": lower_s, "hit": False}

    if store is not None:
        payload = store.get_payload(digest)
        if payload is not None:
            t0 = time.perf_counter()
            try:
                compiled = deserialize_payload(payload)
            except Exception as e:
                metrics.counter(
                    "compile_cache/deserialize_failures_total",
                    help="stored executables that failed to deserialize "
                         "(fell back to compile-and-republish)").inc()
                metrics.warn_once(
                    f"compile-cache-deserialize-{digest[:16]}",
                    f"compile cache entry {digest[:16]} for {name!r} failed "
                    f"to deserialize ({type(e).__name__}: {e}); falling back "
                    "to compile-and-republish")
            else:
                load_s = time.perf_counter() - t0
                metrics.counter(
                    "compile_cache/hits_total",
                    help="executables loaded from the store").inc()
                metrics.histogram(
                    "compile_cache/load_s", buckets=CACHE_TIME_BUCKETS,
                    help="wall time to load+deserialize a stored executable",
                ).observe(load_s)
                if tracer.enabled:
                    tracer.start_span(
                        "compile_cache.load",
                        attributes={"name": name, "fingerprint": digest,
                                    "load_s": load_s},
                    ).end()
                info.update(hit=True, load_s=load_s)
                return compiled, info

    if not compile_on_miss:
        # probe-only mode (eager warm-up on a possibly-cold store): a miss
        # stays LAZY — the consumer keeps the pre-store behavior of
        # compiling on first real use instead of paying an eager compile
        # inside a spin-up path
        info["skipped_compile"] = True
        return None, info

    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    metrics.counter(
        "compile_cache/misses_total",
        help="executables compiled fresh (no loadable store entry)").inc()
    metrics.histogram(
        "compile_cache/compile_s", buckets=CACHE_TIME_BUCKETS,
        help="wall time of fresh backend compiles on the cache-miss path",
    ).observe(compile_s)
    if tracer.enabled:
        tracer.start_span(
            "compile_cache.compile",
            attributes={"name": name, "fingerprint": digest,
                        "compile_s": compile_s},
        ).end()
    info["compile_s"] = compile_s
    if store is not None:
        try:
            payload = serialize_compiled(compiled)
        except Exception as e:
            # an unserializable backend (or future-jax drift) costs the
            # NEXT process a compile, never this one correctness
            metrics.warn_once(
                f"compile-cache-serialize-{name}",
                f"could not serialize executable for {name!r} "
                f"({type(e).__name__}: {e}); entry not published")
        else:
            try:
                store.publish(digest, payload, manifest_extra={
                    "fingerprint": parts,
                    "compile_seconds": round(compile_s, 3),
                    "lower_seconds": round(lower_s, 3),
                    "published_by": name,
                })
            except OSError as e:
                # a full/revoked/contended store costs the NEXT process a
                # recompile — it must never crash the recovery or spin-up
                # path that just compiled successfully
                metrics.warn_once(
                    f"compile-cache-publish-{name}",
                    f"could not publish executable for {name!r} "
                    f"({type(e).__name__}: {e}); entry not stored")
            else:
                info["published"] = True
    return compiled, info


# --------------------------------------------------------------------------- #
# CachedFunction — the drop-in jit wrapper
# --------------------------------------------------------------------------- #


def _shard_tag(sharding: Any) -> Any:
    """In-memory key component for one sharding object (uncached path)."""
    from jax.sharding import SingleDeviceSharding

    if sharding is None or isinstance(sharding, SingleDeviceSharding):
        # single-device == host == abstract (see _sharding_desc); mesh
        # placements stay distinct per (mesh, spec)
        return None
    try:
        return hash(sharding)
    except TypeError:  # pragma: no cover - unhashable future type
        return str(sharding)


class CachedFunction:
    """Wrap a jitted callable with per-signature load-or-compile.

    Call it exactly like the jit fn. The first call at a new signature
    lowers, consults the store, and either loads or compiles (publishing on
    miss); later calls dispatch straight to the resident executable via a
    cheap (treedef, shapes, dtypes, shardings) key. ``static_argnames``
    lists kwargs that are BAKED at lowering time (jit ``static_argnames``)
    — they join the fingerprint by value and are dropped from the call.

    ``_cache_size()`` mirrors the jit private accounting contract
    (``llm/serving.measured_cache_size``), so serving's
    ``compiled_programs`` regression bound keeps counting loaded programs
    exactly like jit-compiled ones.
    """

    def __init__(
        self,
        jit_fn: Callable,
        *,
        name: str,
        store: Optional[ExecutableStore],
        plan: Any = None,
        mesh: Any = None,
        donate_argnums: Sequence[int] = (),
        static_argnums: Sequence[int] = (),
        static_argnames: Sequence[str] = (),
        in_groups: Optional[Sequence[Optional[str]]] = None,
        extra: Any = None,
        metrics=None,
        tracer=None,
    ):
        self._jit_fn = jit_fn
        self.name = name
        self.store = store
        self.plan = plan
        self.mesh = mesh
        self.donate_argnums = tuple(donate_argnums)
        self.static_argnums = tuple(map(int, static_argnums))
        self.static_argnames = tuple(static_argnames)
        self.in_groups = tuple(in_groups) if in_groups is not None else None
        self.extra = extra
        self._metrics = metrics
        self._tracer = tracer
        #: signature key -> (resident executable, load-or-compile info)
        self._by_sig: Dict[Any, Tuple[Any, Dict[str, Any]]] = {}
        #: id(sharding) -> (sharding ref, tag): jax INTERNS sharding
        #: objects across leaves and calls, so the steady-state key costs
        #: one dict hit per leaf instead of isinstance+hash (the ref keeps
        #: the object alive so its id cannot be recycled). Bounded: the
        #: refs pin each sharding's Mesh, and a wrapper surviving many
        #: re-placement epochs would otherwise accumulate retired meshes
        #: forever — on overflow the memo clears and rebuilds.
        self._shard_tags: Dict[int, Tuple[Any, Any]] = {}
        self.last_info: Optional[Dict[str, Any]] = None

    # jit accounting contract (measured_cache_size): resident executables
    def _cache_size(self) -> int:
        return len(self._by_sig)

    def _sig_key(self, args, kwargs) -> Any:
        # HOT: runs once per guarded call on the serving decode path —
        # every per-leaf operation here is a local attr read or dict hit
        flat, treedef = jax.tree_util.tree_flatten((args, kwargs))
        np_shape = np.shape
        tags = self._shard_tags
        leaf_tags = []
        for leaf in flat:
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            sharding = getattr(leaf, "sharding", None)
            if sharding is None:
                tag = None
            else:
                memo = tags.get(id(sharding))
                if memo is None or memo[0] is not sharding:
                    if len(tags) >= 256:
                        tags.clear()
                    memo = (sharding, _shard_tag(sharding))
                    tags[id(sharding)] = memo
                tag = memo[1]
            leaf_tags.append((
                shape if shape is not None else np_shape(leaf),
                dtype if dtype is not None else type(leaf).__name__,
                tag))
        return (treedef, tuple(leaf_tags))

    def _resolve(self, args, kwargs, compile_on_miss: bool = True):
        statics = {k: kwargs[k] for k in self.static_argnames if k in kwargs}
        dyn_kwargs = {k: v for k, v in kwargs.items() if k not in statics}
        pos_statics = {i: args[i] for i in self.static_argnums
                       if i < len(args)}
        dyn_args = tuple(a for i, a in enumerate(args)
                         if i not in pos_statics)
        # statics key by VALUE (they are baked into the program), dynamic
        # args by abstract tag only (they are traced — value-independent)
        key = (tuple(sorted((k, repr(v)) for k, v in statics.items())),
               tuple((i, repr(v)) for i, v in sorted(pos_statics.items())),
               self._sig_key(dyn_args, dyn_kwargs))
        cached = self._by_sig.get(key)
        if cached is None:
            fp_statics = dict(statics)
            fp_statics.update(
                {f"argnum_{i}": v for i, v in pos_statics.items()})
            entry, info = load_or_compile(
                self._jit_fn, args, kwargs,
                name=self.name, store=self.store, plan=self.plan,
                mesh=self.mesh, in_groups=self.in_groups,
                donate_argnums=self.donate_argnums,
                static_args=fp_statics, extra=self.extra,
                metrics=self._metrics, tracer=self._tracer,
                compile_on_miss=compile_on_miss,
            )
            self.last_info = info
            if entry is None:  # probe-only miss: nothing resident yet
                return None, info, dyn_args, dyn_kwargs
            cached = (entry, info)
            self._by_sig[key] = cached
        return cached[0], cached[1], dyn_args, dyn_kwargs

    def prepare(self, *args, only_cached: bool = False,
                **kwargs) -> Dict[str, Any]:
        """Load-or-compile for this signature WITHOUT calling — ``args``
        may be abstract (``ShapeDtypeStruct`` trees), which lower to the
        SAME fingerprint as host-resident concrete args. Replica spin-up
        uses this to warm its programs eagerly instead of paying the
        compile (or load) on the first real request. ``only_cached=True``
        loads when the store has the fingerprint and otherwise stays LAZY
        (no eager compile — the autoscaler's cold-store spin-up must not
        be slower than the pre-store first request was). Returns the
        load-or-compile info for the resolved signature."""
        _, info, _, _ = self._resolve(args, kwargs,
                                      compile_on_miss=not only_cached)
        return info

    def __call__(self, *args, **kwargs):
        entry, _, dyn_args, dyn_kwargs = self._resolve(args, kwargs)
        # baked statics (positional and keyword) are dropped at call time
        return entry(*dyn_args, **dyn_kwargs)
