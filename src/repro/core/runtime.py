"""Task runtime — the CEDR analogue RIMMS integrates with (§2, §3.2.2).

A small dynamic task runtime: applications submit *API calls* (tasks) over
:class:`~repro.core.hete.HeteData` buffers; a scheduler maps each task to a
processing element (PE) at dispatch time (round-robin, pinned,
data-affinity, or transfer-aware HEFT-lite); the memory policy decides
what data movement happens.

Two memory policies, both first-class so every experiment reports the pair:

* ``"reference"`` — the paper's baseline (host-owned data): every input is
  copied host→PE before execution and every output PE→host after, so the
  host always holds the valid copy (Fig 1a).
* ``"rimms"``     — the paper's contribution: per-input last-resource-flag
  check, direct src→PE copy only when the flag names another location,
  output flag update to the executing PE (Fig 1b).

Tasks reach PEs through one path: the streaming session API
(:mod:`repro.core.api`) — ``@rimms.op``-registered kernels,
``Session.malloc``/``Session.submit`` returning
:class:`~repro.core.api.BufferFuture` handles — whose persistent
:class:`~repro.core.executor.StreamExecutor` consumes the task stream
continuously.  This class is the **dispatch engine behind it**: the
stage → launch → commit steps each task goes through, the scheduler
cost bases, and the kernel registry.

PEs are emulated on this CPU-only box: a "cpu" PE executes numpy
callables against host memory; accelerator PEs ("fft_acc", "zip_acc",
"gpu") execute jitted JAX callables against their own
:class:`~repro.core.hete.MemorySpace`. Transfers between spaces are real
array movements and are recorded in the ledger (count, bytes, modeled
seconds under platform bandwidths).
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import os
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .graph import CostModel
from .hete import HeteContext, HeteData, MemorySpace
from .locations import HOST, Location
from .telemetry import DivergenceMonitor
from .trace import NULL_REGION

__all__ = ["PE", "Task", "Runtime", "make_emulated_soc", "SCHEDULERS",
           "BACKENDS", "resolve_backend", "register_platform",
           "platform_names"]

# ---------------------------------------------------------------------------
# Execution backends (ISSUE 7) — one knob, threaded everywhere
# ---------------------------------------------------------------------------

#: valid values for the ``backend=`` knob (Session / Session.emulated /
#: Runtime / make_emulated_soc).
BACKENDS = ("thread", "process", "auto")


def resolve_backend(backend: Optional[str]) -> str:
    """Validate + resolve a backend name to ``"thread"`` or ``"process"``.

    ``None`` means thread (the historical default).  ``"auto"`` means
    thread on a TPU, whose chips belong to this process and no worker
    can reach; elsewhere it picks the process backend when real
    parallelism is available — more than one CPU core, or more than one
    JAX device — and thread otherwise."""
    if backend is None:
        return "thread"
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown backend {backend!r}: choose one of {BACKENDS}")
    if backend == "auto":
        import jax

        if jax.default_backend() == "tpu":
            return "thread"
        if (os.cpu_count() or 1) > 1 or len(jax.devices()) > 1:
            return "process"
        return "thread"
    return backend


# ---------------------------------------------------------------------------
# Platform-preset shorthand registry (ISSUE 7 satellite, carried from PR 4)
# ---------------------------------------------------------------------------

# name -> (topology_factory(dev_locs) -> Topology | None, arena_bytes | None)
_PLATFORMS: Dict[str, tuple] = {}


def register_platform(name: str, topology_factory: Optional[Callable] = None,
                      arena_bytes: Optional[int] = None, *,
                      replace: bool = False) -> None:
    """Register a platform preset so ``Session.emulated("name")`` (and
    ``make_emulated_soc(topology="name")``) resolves it.

    ``topology_factory(dev_locs)`` returns the
    :class:`~repro.core.topology.Topology` for the platform's device
    locations (``None`` keeps the scalar bandwidth model);
    ``arena_bytes`` is the preset's default per-accelerator arena
    capacity (callers may still override it).  Built-in presets mirror
    :data:`repro.core.topology.PRESETS`; re-registering a name raises
    unless ``replace=True``."""
    _register_builtin_platforms()
    if not replace and name in _PLATFORMS:
        raise ValueError(f"platform {name!r} already registered "
                         f"(pass replace=True to override)")
    _PLATFORMS[name] = (topology_factory, arena_bytes)


def platform_names() -> Tuple[str, ...]:
    """Registered platform preset names (built-ins + user presets)."""
    _register_builtin_platforms()
    return tuple(sorted(_PLATFORMS))


def _resolve_platform(name: str):
    """The registry entry for ``name`` or None (fall through to the raw
    topology presets for back-compat)."""
    _register_builtin_platforms()
    return _PLATFORMS.get(name)


def _register_builtin_platforms() -> None:
    # Lazy (first use), so importing this module never imports topology.
    from .topology import PRESETS, build_preset

    for preset in PRESETS:
        _PLATFORMS.setdefault(
            preset,
            (lambda locs, _p=preset: build_preset(_p, locs), 64 << 20),
        )

SCHEDULERS = ("round_robin", "data_affinity", "heft")

#: kernel-launch ids, unique in the process: runtimes traced into one
#: collector may name their PEs alike
_LAUNCH_IDS = itertools.count(1)


@dataclasses.dataclass
class PE:
    """A processing element: name, kind, its memory location, supported ops."""

    name: str
    kind: str  # "cpu" | "acc" | "gpu" | ...
    location: Location
    supports: frozenset

    def __post_init__(self) -> None:
        self.supports = frozenset(self.supports)


@dataclasses.dataclass
class Task:
    """One API call: op over HeteData inputs/outputs (+ scalar params)."""

    op: str
    inputs: List[HeteData]
    outputs: List[HeteData]
    params: Dict[str, Any] = dataclasses.field(default_factory=dict)
    pin: Optional[str] = None  # pin to a PE name (CPU-ACC style scenarios)
    name: str = ""
    # submitting session client (ISSUE 5): per-tenant accounting +
    # cross-client interference-aware placement key on it
    client: Optional[str] = None

    @property
    def in_bytes(self) -> int:
        return sum(hd.nbytes for hd in self.inputs)

    @property
    def out_bytes(self) -> int:
        return sum(hd.nbytes for hd in self.outputs)


class Runtime:
    """Dispatch loop: schedule → move (policy) → execute → flag update."""

    def __init__(
        self,
        pes: Sequence[PE],
        context: HeteContext,
        *,
        policy: str = "rimms",
        scheduler: str = "round_robin",
        cost_model: Optional[CostModel] = None,
        backend: Optional[str] = None,
    ) -> None:
        if policy not in ("rimms", "reference"):
            raise ValueError(f"unknown memory policy {policy!r}")
        if scheduler not in SCHEDULERS:
            raise ValueError(f"unknown scheduler {scheduler!r}")
        #: "thread" (in-process kernels) or "process" (subprocess PE
        #: workers for host-payload PEs, ISSUE 7); "auto" resolves here.
        self.backend = resolve_backend(backend)
        self.pes = list(pes)
        self.by_name = {pe.name: pe for pe in self.pes}
        self.context = context
        self.policy = policy
        self.scheduler = scheduler
        self.cost_model = cost_model or CostModel()
        # Measured-vs-modeled divergence (ISSUE 8): every compute/stage
        # execution pairs its wall duration with the cost model's prior
        # into per-(op, PE kind, shape bucket) ratio cells — surfaced in
        # Session.qos_report()["divergence"] and bench JSON records.
        self.divergence = DivergenceMonitor()
        self._rr_state: Dict[str, int] = {}
        # kernels: (op, pe_kind) -> callable(list_of_arrays, **params) -> tuple
        self._kernels: Dict[tuple, Callable] = {}
        # tuned kernel variants (ISSUE 10): (op, pe_kind, variant name)
        # -> (callable, bound launch params) — dispatched when an
        # attached calibration table names a winner for the shape bucket
        self._variant_kernels: Dict[tuple, Tuple[Callable, dict]] = {}
        #: attached CalibrationTable (None = default dispatch + priors)
        self.calibration = None
        #: non-default variant dispatches, (op, pe kind, variant) per
        #: call — outputs are bit-identical by construction, so tests
        #: and benches assert selection through this log
        self.variant_log: List[tuple] = []
        self.task_log: List[tuple] = []  # (task name/op, pe name) for tests
        # persistent per-PE worker pool, created lazily by the first
        # session and reused by later ones (ISSUE 2); close() releases it
        self._worker_pool = None
        # per-PE subprocess workers (ISSUE 7), created lazily on the
        # first process-dispatched kernel; close() reaps them
        self._process_pool = None

    def set_backend(self, backend: Optional[str]) -> str:
        """Re-resolve the execution backend (e.g. a Session adopting this
        runtime with an explicit ``backend=``).  Returns the resolved
        name; an unknown name raises listing the valid choices."""
        if backend is not None:
            self.backend = resolve_backend(backend)
        return self.backend

    def _get_worker_pool(self):
        from .executor import WorkerPool  # local import: avoids cycle

        if self._worker_pool is None:
            pool = WorkerPool(self.pes)
            self._worker_pool = pool
            # release the pool's threads when this Runtime is collected
            self._pool_finalizer = weakref.finalize(
                self, WorkerPool.shutdown, pool
            )
        return self._worker_pool

    def _get_process_pool(self):
        from .pworker import ProcessWorkerPool  # local import: avoids cycle

        if self._process_pool is None:
            pool = ProcessWorkerPool()
            self._process_pool = pool
            # reap subprocesses when this Runtime is collected
            self._ppool_finalizer = weakref.finalize(
                self, ProcessWorkerPool.shutdown, pool
            )
        return self._process_pool

    def close(self) -> None:
        """Release the persistent worker pool and reap every PE worker
        subprocess (idempotent)."""
        if self._worker_pool is not None:
            self._pool_finalizer.detach()
            self._worker_pool.shutdown()
            self._worker_pool = None
        if self._process_pool is not None:
            self._ppool_finalizer.detach()
            self._process_pool.shutdown()
            self._process_pool = None

    # -- registration -------------------------------------------------------
    def register_kernel(self, op: str, pe_kind: str, fn: Callable, *,
                        variant: Optional[str] = None,
                        params: Optional[Dict[str, Any]] = None) -> None:
        """Register a kernel.  Without ``variant`` this is the op's
        default (reference) kernel — the historical behavior every call
        site relies on.  With ``variant`` it is a tuned candidate
        (ISSUE 10): ``params`` are its launch parameters, merged *under*
        per-task params at dispatch; it only runs when the attached
        calibration table names it the winner for the task's shape
        bucket."""
        if variant is None:
            self._kernels[(op, pe_kind)] = fn
        else:
            self._variant_kernels[(op, pe_kind, variant)] = (
                fn, dict(params or {}))

    def set_calibration(self, table) -> None:
        """Attach a :class:`~repro.core.calibrate.CalibrationTable`:
        the cost model prices from its measured cells
        (:meth:`CostModel.set_calibration
        <repro.core.graph.CostModel.set_calibration>`) and
        :meth:`_run_kernel` dispatches its winning variants.  ``None``
        detaches (default priors + default kernels)."""
        self.calibration = table
        self.cost_model.set_calibration(table)

    # -- scheduling -----------------------------------------------------------
    def _eligible(self, task: Task) -> List[PE]:
        pes = [
            pe
            for pe in self.pes
            if task.op in pe.supports and (task.op, pe.kind) in self._kernels
        ]
        if not pes:
            raise LookupError(f"no PE supports op {task.op!r}")
        return pes

    def _schedule(self, task: Task) -> PE:
        """Static placement at admission under ``round_robin`` (paper
        §5.4): the pinned PE, else the next eligible PE in a per-op
        rotation."""
        if task.pin is not None:
            return self.by_name[task.pin]
        pes = self._eligible(task)
        i = self._rr_state.get(task.op, 0)
        self._rr_state[task.op] = (i + 1) % len(pes)
        return pes[i % len(pes)]

    def _affinity_pick(self, task: Task, pes: Sequence[PE]) -> PE:
        """Most input bytes already valid at the PE; ties broken by stable
        PE-name ordering (deterministic)."""
        def score(pe: PE) -> int:
            return sum(
                hd.nbytes for hd in task.inputs if hd.last_location == pe.location
            )
        return min(pes, key=lambda pe: (-score(pe), pe.name))

    def _heft_costs(self, task: Task, pe: PE) -> Tuple[float, float]:
        """(modeled input-transfer seconds, estimated compute seconds) for
        placing ``task`` on ``pe`` — the EFT cost basis of the stream's
        HEFT placement."""
        bw = self.context.ledger.bandwidth_model
        tr = sum(
            bw.seconds(hd.last_location, pe.location, hd.nbytes)
            for hd in task.inputs
            if hd.last_location != pe.location
        )
        return tr, self.cost_model.estimate(task.op, pe.kind, task.in_bytes)

    # -- stage → launch → commit ---------------------------------------------
    def _pin_inputs(self, task: Task, loc: Location) -> None:
        """Hard-pin every input's root at ``loc`` so eviction triggered by
        a concurrent (or this task's own output) reservation can never
        spill bytes the kernel is about to read.  Balanced by
        :meth:`_unpin_inputs` after commit."""
        for hd in task.inputs:
            self.context.pin(hd, loc)

    def _unpin_inputs(self, task: Task, loc: Location) -> None:
        for hd in task.inputs:
            self.context.unpin(hd, loc)

    def _stage_inputs(
        self, task: Task, pe: PE, *, prefetch: bool = False
    ) -> Tuple[List[Any], float, float, List[tuple]]:
        """Materialize ``task``'s inputs at ``pe`` under the memory policy.
        Returns (input values, modeled transfer seconds, modeled seconds
        stalled on eviction write-backs, list of performed copies as
        ``(src, dst, nbytes)`` — the executor's topology replay re-prices
        these under per-link contention).

        Demand mode (default): inputs stay hard-pinned at ``pe`` until
        :meth:`_unpin_inputs` — callers release after commit.  Only one
        PE worker reserves per arena, so pinned bytes are bounded by one
        launch's working set (:meth:`batch_fits`).

        Prefetch mode: *speculative warming* — runs under the context's
        prefetch guard (raises :class:`~repro.core.hete.PrefetchDeferred`
        instead of evicting pinned/protected bytes) and takes NO pins, so
        concurrent prefetches can never starve the demand path.  The PE
        worker re-stages authoritatively before executing: a free flag
        hit when the warmed bytes survived, a re-fetch if pressure
        evicted them in between."""
        ctx, loc = self.context, pe.location
        ins: List[Any] = []
        model_s = 0.0
        ctx.take_spill_seconds()  # clear this thread's residue
        ctx.take_moves()  # arm + clear this thread's move log
        moves: List[tuple] = []
        if not prefetch:
            self._pin_inputs(task, loc)
        try:
            if self.policy == "reference":
                # Host-owned: host is current (producer wrote host under
                # this policy); copy host→PE unconditionally.
                for hd in task.inputs:
                    with hd.lock:
                        host_val = hd.copies[HOST]
                        if loc != HOST:
                            with ctx.copy_region(HOST, loc, hd.nbytes):
                                moved = ctx.spaces[loc].ingest(host_val)
                                model_s += ctx.record_copy(HOST, loc, hd.nbytes)
                            moves.append((HOST, loc, hd.nbytes))
                            ins.append(moved)
                        else:
                            ins.append(host_val)
            else:  # rimms: flag check + direct src→PE copy when needed
                guard = (ctx.prefetch_guard() if prefetch
                         else contextlib.nullcontext())
                with guard:
                    for hd in task.inputs:
                        value, tr_s = ctx.stage(hd, loc)
                        ins.append(value)
                        model_s += tr_s
                moves = ctx.take_moves()
        except BaseException:
            if not prefetch:
                self._unpin_inputs(task, loc)
            raise
        return ins, model_s, ctx.take_spill_seconds(), moves

    def _proc_eligible(self, pe: PE) -> bool:
        """Whether ``pe``'s kernels may execute in a subprocess worker:
        its memory space must hold host-format payloads (see
        :attr:`~repro.core.hete.MemorySpace.proc_exec`) — PEs bound to a
        real JAX device keep in-process async dispatch."""
        space = self.context.spaces.get(pe.location)
        return space is not None and getattr(space, "proc_exec", False)

    def _in_process(self, pe: PE) -> bool:
        return not (self.backend == "process" and self._proc_eligible(pe))

    def _run_kernel(self, task: Task, pe: PE, ins: List[Any]) -> Tuple[tuple, float]:
        """Call one task's kernel inside a :meth:`_launch`, its one
        caller; returns (outputs, seconds).  In process the call returns
        once the kernel is dispatched — JAX outputs may still be
        computing, the launch waits for them and times itself — and the
        seconds are 0.

        Backend dispatch (ISSUE 7): under ``backend="process"`` the call
        runs on ``pe``'s subprocess worker — shared-memory inputs map
        zero-copy, the parent thread blocks GIL-free on the reply — for
        every PE whose space holds host payloads, and the seconds are the
        worker's; other PEs (real JAX devices) execute in-process."""
        if not self._in_process(pe):
            return self._run_kernel_process(task, pe, ins)
        fn, params, _ = self._select_kernel(task, pe)
        return _as_tuple(fn(ins, **params)), 0.0

    def batch_fits(self, tasks: Sequence[Task], pe: PE) -> int:
        """How many of ``tasks``, taken in order, one launch on ``pe``
        carries: as many as ``pe``'s arena holds without evicting — the
        roots their inputs and outputs would newly reserve fit in its
        free bytes.  The first task always goes."""
        n = len(tasks)
        space = self.context.spaces.get(pe.location)
        if space is None or space.arena is None:
            return n
        free = space.arena.free_bytes
        seen: set = set()
        for k, task in enumerate(tasks):
            for hd in task.inputs + task.outputs:
                root = hd.root
                if id(root) not in seen and pe.location not in root.extents:
                    seen.add(id(root))
                    free -= root.nbytes
            if free < 0 and k > 0:
                return k
        return n

    def _launch(self, tasks: Sequence[Task], pe: PE,
                ins_list: Sequence[List[Any]]) -> list:
        """One kernel launch on ``pe`` carrying ``tasks`` with their
        staged ``ins_list``: each task's ``compute`` region is open
        around it, with the launch's id and size; each task's kernel is
        called (:meth:`_run_kernel`), then an in-process device PE waits
        once for all their outputs.  Returns, per task, ``(outputs,
        seconds)`` — in process, the launch's seconds over its number of
        tasks — or the exception that failed that task alone.  Those
        seconds feed the cost model and the divergence monitor."""
        n = len(tasks)
        launch = next(_LAUNCH_IDS)
        in_process = self._in_process(pe)
        with contextlib.ExitStack() as regions:
            for task in tasks:
                regions.enter_context(self._region(
                    "compute", task, pe, launch=launch, batch=n))
            t0 = time.perf_counter()
            runs = [_attempt(self._run_kernel, task, pe, ins)
                    for task, ins in zip(tasks, ins_list)]
            if in_process and pe.location != HOST:
                runs = _wait_for_device(runs)
            dt = (time.perf_counter() - t0) / n
        for k, (task, run) in enumerate(zip(tasks, runs)):
            if isinstance(run, BaseException):
                continue
            if in_process:
                run = runs[k] = (run[0], dt)
            self.cost_model.observe(task.op, pe.kind, task.in_bytes, run[1])
            self.divergence.observe(
                "compute", task.op, pe.kind, task.in_bytes, run[1],
                self.cost_model.prior_estimate(task.op, pe.kind, task.in_bytes))
        return runs

    def _select_kernel(self, task: Task, pe: PE) -> Tuple[Callable, dict, str]:
        """Variant-aware kernel lookup (ISSUE 10): the attached
        calibration table's winning variant for the task's shape bucket
        when it is registered (bit-identical to the default by the
        autotuner's eligibility bar), else the default kernel.  Returns
        ``(fn, merged params, variant name)`` — per-task params override
        the variant's bound launch params.  Non-default selections are
        appended to :attr:`variant_log`."""
        if self.calibration is not None:
            vname = self.calibration.best_variant(task.op, pe.kind,
                                                  task.in_bytes)
            if vname is not None:
                entry = self._variant_kernels.get((task.op, pe.kind, vname))
                if entry is not None:
                    fn, vparams = entry
                    self.variant_log.append((task.op, pe.kind, vname))
                    return fn, {**vparams, **task.params}, vname
        return self._kernels[(task.op, pe.kind)], dict(task.params), "default"

    def _run_kernel_process(self, task: Task, pe: PE,
                            ins: List[Any]) -> Tuple[tuple, float]:
        """Process-backend kernel call: ship handles to ``pe``'s worker,
        forward the worker-measured compute span onto the trace (on the
        ``pe:{name}:worker`` track, clock-offset corrected and clamped to
        the parent-observed call window)."""
        fn, params, vname = self._select_kernel(task, pe)
        key = (task.op, pe.kind, vname)
        worker = self._get_process_pool().worker(pe.name)
        worker.ensure_kernel(key, fn)
        outs, w0, w1, k0, k1 = worker.run(key, ins, params)
        dt = w1 - w0
        tracer = self.context.tracer
        if tracer is not None:
            tracer.forward_span(
                task.name or task.op, "compute", f"pe:{pe.name}:worker",
                k0, k1, lo=w0, hi=w1,
                args={"op": task.op, "backend": "process",
                      "worker_pid": worker.pid},
            )
        return outs, dt

    def _commit_outputs(self, task: Task, pe: PE, outs: tuple) -> Tuple[float, float]:
        """Flag updates (+ host writeback under reference). Returns
        (modeled output-transfer seconds, modeled eviction-stall seconds
        the output reservations caused)."""
        ctx, loc = self.context, pe.location
        model_s = 0.0
        ctx.take_spill_seconds()  # clear this thread's residue
        with self._region("writeback", task, pe):
            if self.policy == "reference":
                for hd, val in zip(task.outputs, outs):
                    if loc != HOST:
                        with ctx.copy_region(loc, HOST, hd.nbytes):
                            host_val = ctx.spaces[loc].egress(val)
                            model_s += ctx.record_copy(loc, HOST, hd.nbytes)
                    else:
                        host_val = np.asarray(val)
                    ctx.mark_written(hd, HOST, host_val.reshape(hd.shape))
            else:
                for hd, val in zip(task.outputs, outs):
                    ctx.mark_written(hd, loc, val)
        return model_s, ctx.take_spill_seconds()

    def _region(self, cat: str, task: Task, pe: PE, **stats):
        """The tracer's region for one phase of ``task`` on ``pe`` —
        ``stage``, ``compute`` or ``writeback``, on the PE's track, with
        ``stats`` beside the task's own (``prefetch=1``; a compute's
        ``launch`` and ``batch``) — or the shared null context when
        tracing is off."""
        tracer = self.context.tracer
        if tracer is None:
            return NULL_REGION
        tname = task.name or task.op
        track = f"pe:{pe.name}:stage" if cat == "stage" else f"pe:{pe.name}"
        return tracer.region(tname, cat, track, task=tname, op=task.op,
                             pe=pe.name, **stats)


def _attempt(fn: Callable, *args) -> Any:
    """``fn(*args)``, or the exception it raised."""
    try:
        return fn(*args)
    except Exception as e:
        return e


def _wait_for_device(runs: list) -> list:
    """Wait once for the outputs of every ``(outputs, seconds)`` in
    ``runs``; if that wait raises, wait for each alone, so that only the
    runs whose outputs failed become their exception."""
    import jax

    def ready(run: tuple) -> tuple:
        jax.block_until_ready(run[0])
        return run

    try:
        jax.block_until_ready([r[0] for r in runs
                               if not isinstance(r, BaseException)])
        return runs
    except Exception:
        return [r if isinstance(r, BaseException) else _attempt(ready, r)
                for r in runs]


def _as_tuple(x: Any) -> tuple:
    return x if isinstance(x, tuple) else (x,)


# ---------------------------------------------------------------------------
# Emulated heterogeneous SoC (§4.1 analogue) — built on the single CPU
# device: accelerator memory spaces hold jax.Arrays, host space numpy.
# ---------------------------------------------------------------------------


def make_emulated_soc(
    *,
    n_cpu: int = 1,
    accelerators: Sequence[str] = ("fft_acc0", "zip_acc0"),
    acc_ops: Optional[Dict[str, Sequence[str]]] = None,
    arena_bytes=64 << 20,  # 64 MiB UDMA buffer, as on the ZCU102
    allocator: str = "nextfit",
    block_size: int = 4096,
    context: Optional[HeteContext] = None,
    tracking: str = "flag",
    topology=None,
    backend: Optional[str] = None,
    host_arena_bytes: Optional[int] = None,
) -> tuple:
    """Build (runtime-ready PEs, HeteContext) for an emulated SoC.

    ``acc_ops`` maps accelerator name → ops it supports; defaults derive
    from the name prefix ("fft_acc*" → fft/ifft, "zip_acc*" → zip,
    "gpu*" → everything).

    ``arena_bytes`` is one capacity for every accelerator, or a dict
    ``{accelerator name: bytes}`` for asymmetric arenas (spill-to-peer
    scenarios need a roomy neighbour).

    ``topology`` opts into routed, contention-aware transfer modeling
    (ISSUE 3): a platform name from :func:`platform_names` (built-ins
    "emulated_soc", "pcie_tree", "nvlink_mesh", "host_bridged_fpga", plus
    anything the embedding app added via :func:`register_platform`), a
    :class:`~repro.core.topology.Topology`, or a ready
    :class:`~repro.core.topology.TopologyBandwidthModel`.  It replaces
    the context ledger's scalar bandwidth model; ``None`` (the default)
    keeps the scalar model, so existing baselines hold.

    ``backend`` (ISSUE 7): ``"thread"`` (default) keeps in-process
    kernels over per-device jax payloads.  ``"process"`` builds the SoC
    for subprocess PE workers: host buffers come from a
    :class:`~repro.core.shm.SharedHostArena` (``host_arena_bytes``
    capacity) that workers map zero-copy, and on a single CPU device the
    emulated accelerator spaces hold host-format numpy payloads (their
    arenas — capacity, eviction, the whole ledger — stay modeled exactly
    as before).  On a TPU, or when ``jax.devices()`` exposes more than
    one device, accelerators are spread round-robin across the devices
    and keep in-process async dispatch: a worker cannot reach a chip
    this process holds, and real device parallelism beats a worker pipe.
    """
    import jax

    backend = resolve_backend(backend)
    ctx = context or HeteContext(tracking=tracking)
    devices = jax.devices()
    host_payloads = (backend == "process" and len(devices) == 1
                     and jax.default_backend() != "tpu")
    if backend == "process" and ctx.host_arena is None:
        from .shm import SharedHostArena, default_arena_bytes

        ctx.attach_host_arena(SharedHostArena(
            host_arena_bytes or default_arena_bytes()))

    def _egress(value) -> np.ndarray:
        return np.asarray(value)

    pes: List[PE] = []
    for i in range(n_cpu):
        pes.append(
            PE(f"cpu{i}", "cpu", HOST, frozenset({"fft", "ifft", "zip", "generic"}))
        )

    default_ops = {"fft_acc": ("fft", "ifft"), "zip_acc": ("zip",),
                   "gpu": ("fft", "ifft", "zip", "generic")}
    dev_locs: List[Location] = []
    for idx, name in enumerate(accelerators):
        kind = next((k for k in default_ops if name.startswith(k)), "acc")
        ops = tuple((acc_ops or {}).get(name, default_ops.get(kind, ())))
        loc = Location("device", name)
        dev_locs.append(loc)
        capacity = (
            arena_bytes.get(name, 64 << 20)
            if isinstance(arena_bytes, dict) else arena_bytes
        )
        if host_payloads:
            # Subprocess workers execute this PE's kernels: device copies
            # are host-format (distinct shared-memory buffers — the
            # host→device copy is real, the arena stays modeled).
            ingest = ctx.host_copy
            proc_exec = True
        else:
            device = devices[idx % len(devices)]
            ingest = (lambda v, _d=device: jax.device_put(v, _d))
            proc_exec = False
        ctx.register_space(
            MemorySpace(
                loc,
                capacity=capacity,
                allocator=allocator,
                block_size=block_size,
                ingest=ingest,
                egress=_egress,
                proc_exec=proc_exec,
            )
        )
        pes.append(PE(name, "gpu" if kind == "gpu" else "acc", loc, frozenset(ops)))

    if topology is not None:
        from .topology import Topology, TopologyBandwidthModel, build_preset

        if isinstance(topology, str):
            entry = _resolve_platform(topology)
            if entry is not None and entry[0] is not None:
                topology = entry[0](dev_locs)
            else:
                topology = build_preset(topology, dev_locs)
        if isinstance(topology, Topology):
            topology = TopologyBandwidthModel(topology)
        ctx.ledger.bandwidth_model = topology
    return pes, ctx
