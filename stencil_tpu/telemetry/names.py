"""Canonical telemetry names — THE single registry of every metric, span,
and event name this tree may emit.

Every call into the telemetry facade (``telemetry.inc`` / ``observe`` /
``set_gauge`` / ``emit_event`` / ``span``) must name its
series through a constant defined here; the ``telemetry-name`` rule of
``stencil_tpu.lint`` (wired as a tier-1 test) rejects free-string names at
call sites.  One
module of constants keeps the cross-round BENCH diffs stable: a renamed or
typo'd series fails the lint instead of silently forking the time series.

Naming scheme: ``<subsystem>.<noun>[.<unit>]``, lowercase, dots between
levels, underscores inside a level.  Histograms of seconds end in
``.seconds``; byte counters end in ``.bytes``.
"""

from __future__ import annotations

# --- counters (monotonic; always recorded, snapshot seeds all of these) -----

#: halo exchanges accounted: direct ``exchange()``/``exchange_many()`` calls,
#: plus those a ``run_step`` dispatch makes by the step's OWN account
#: (``ops/exchange.WireAccount``, declared by its builder from the message
#: plan it runs: three a time step of the staged MHD step, one a macro of a
#: wavefront, none on a wrap route; a caller's own step callable that
#: declares nothing falls back on one a macro)
EXCHANGE_COUNT = "domain.exchange.count"
#: bytes those exchanges send over WIRES, summed over subdomains: always the
#: sum of the ``exchange.hop.*.bytes`` counters, 0 on one chip -- a ``run_step``
#: dispatch's from its declared account, a direct call's (and the undeclared
#: step's fallback) from the domain's own.  The analytic
#: ``exchange_bytes_total`` (every shell cell, the reference's
#: exchange_bytes_for_method accounting) is the ``domain.exchange`` span's
#: ``nbytes`` and the gauge ``EXCHANGE_BYTES_PER_EXCHANGE``
EXCHANGE_BYTES = "domain.exchange.bytes"
#: ``run_step`` dispatches (device-side loops of many raw iterations)
STEP_DISPATCHES = "domain.step.dispatches"
#: raw stencil iterations advanced through ``run_step``
STEP_ITERATIONS = "domain.step.iterations"
#: transient-failure retry attempts (resilience/retry.py)
RETRY_ATTEMPTS = "resilience.retry.attempts"
#: retries abandoned after exhausting the policy budget
RETRY_EXHAUSTED = "resilience.retry.exhausted"
#: retries refused by the donated-buffer liveness guard
RETRY_REFUSED = "resilience.retry.refused"
#: degradation-ladder descents (resilience/ladder.py)
LADDER_DESCENTS = "resilience.ladder.descents"
#: faults raised by the STENCIL_FAULT_PLAN hook (resilience/inject.py)
FAULTS_INJECTED = "resilience.faults.injected"
#: divergence-sentinel NaN/Inf detections (resilience/sentinel.py)
SENTINEL_TRIPS = "resilience.sentinel.trips"
#: tuned-config cache consultations that found a persisted config (tune/)
TUNE_CACHE_HIT = "tune.cache.hit"
#: consultations that found nothing (cold cache, stale version, corrupt file)
TUNE_CACHE_MISS = "tune.cache.miss"
#: candidate configs actually measured by the autotuner's trial protocol
TUNE_TRIALS = "tune.trials"
#: candidates pruned without a steady-state measurement (VMEM model
#: pre-filter, or an on-device VMEM_OOM/COMPILE_REJECT pruning the candidate
#: and its deeper neighbors)
TUNE_PRUNED = "tune.pruned"
#: winning configs selected (and persisted) by a completed search
TUNE_SELECTED = "tune.selected"
#: analytic bytes moved through packed shell message buffers (the ``zpack_*``
#: / ``yzpack_*`` exchange routes; 0 under ``direct`` — ops/exchange.py
#: ``z/ypack_message_stats``), of the exchanges ``EXCHANGE_COUNT`` counts and
#: from the same account (``WireAccount.packed``: the quantities a step's
#: exchange carries)
EXCHANGE_PACKED_BYTES = "exchange.packed.bytes"
#: analytic pack+unpack kernel launches of those packed exchanges
EXCHANGE_PACKED_KERNELS = "exchange.packed.kernels"
#: JOINT sweeps dispatched, summed over subdomains: sweeps of a halo exchange in
#: which two wired axes' faces were cut from the entering blocks and sent at
#: once, the corner strips relayed behind them (ops/exchange.py
#: ``_sweep_groups``; one an exchange on mesh [2,2,1], none where fewer than
#: two wired ``direct`` sweeps follow each other) -- of the exchanges
#: ``EXCHANGE_COUNT`` counts and from the same account (``WireAccount.joint``)
EXCHANGE_JOINT_SWEEPS = "exchange.joint.sweeps"
#: analytic boundary-band cells RECOMPUTED by the split-step exterior passes
#: (``overlap=split`` on the stream engine, ops/stream.py): the redundant
#: surface work the overlapped schedule pays to free the interior pass from
#: any ppermute dependency; 0 under ``overlap=off``
STEP_OVERLAP_EXTERIOR_CELLS = "step.overlap.exterior_cells"
#: checkpoints committed (atomic rename completed — io/checkpoint.py)
CHECKPOINT_SAVES = "checkpoint.saves"
#: bytes of quantity data written by those checkpoints (interior cells at
#: the NATIVE dtype — the portable representation the digests cover)
CHECKPOINT_SAVE_BYTES = "checkpoint.save.bytes"
#: successful checkpoint restores (elastic cross-mesh restores included)
CHECKPOINT_RESTORES = "checkpoint.restores"
#: checkpoints REJECTED by validation (missing/partial manifest, digest
#: mismatch) — each one the retention-ring fallback skipped past
CHECKPOINT_INVALID = "checkpoint.invalid"
#: supervisor restarts from the last valid checkpoint after a FATAL/STALL
#: dispatch classification (resilience/supervisor.py restart budget)
SUPERVISOR_RESTARTS = "supervisor.restarts"
#: watchdog deadline trips (resilience/watchdog.py): dispatches that ran
#: past STENCIL_WATCHDOG_S without completing
WATCHDOG_STALLS = "watchdog.stalls"
#: device-profile captures taken by the cadence profiler
#: (telemetry/device.py ``ProfileCapture`` — STENCIL_PROFILE_EVERY /
#: ``--profile-dir``); 0 when profiling is off or the backend has no
#: profiler (the capture degrades to a warn, never a crash)
PROFILE_CAPTURES = "profile.captures"
#: completed in-memory mesh transitions (``DistributedDomain.reshard`` —
#: parallel/redistribute.py): live grow/shrink moves that never touched
#: disk; the checkpoint-elastic-restore fallback counts separately below
RESHARDS = "reshard.count"
#: analytic bytes of interior state moved by those resharding collectives
#: (whole valid interior at the stored dtype, every quantity)
RESHARD_BYTES = "reshard.bytes"
#: capacity changes that could NOT reshard in memory and fell back to
#: checkpoint-elastic-restore (devices gone, no admissible partition,
#: consumed buffers) — each one also charges the supervisor restart budget
RESHARD_FALLBACKS = "reshard.fallbacks"
#: fused on-device field-health snapshots taken (telemetry/numerics.py
#: ``NumericsEngine.snapshot`` — one sharded dispatch, O(#quantities)
#: scalars to the host; the cadence paths STENCIL_NUMERICS_EVERY and the
#: rewired divergence sentinel both count here)
NUMERICS_SNAPSHOTS = "numerics.snapshots"
#: guardband violations observed over those snapshots (the invariant
#: drifted but stayed finite — observe-only unless STENCIL_NUMERICS_ABORT
#: escalates).  Doubles as the event name: one constant, one series.
NUMERICS_DRIFT = "numerics.drift"
#: serving-layer requests ADMITTED past admission control (serve/server.py:
#: VMEM verdict ok, executable warm or compiled under budget, queue slot)
SERVE_ADMITTED = "serve.admitted"
#: requests REFUSED at admission (static VMEM verdict, cold compile over
#: budget, queue full, tenant quarantined/evicted)
SERVE_REJECTED = "serve.rejected"
#: queued requests SHED under load (past-deadline first, then lowest
#: priority to make room for a higher-priority arrival)
SERVE_SHED = "serve.shed"
#: tenants evicted/quarantined by the per-tenant fault envelope (their
#: DIVERGENCE — a poisoned request — must not touch other tenants)
SERVE_EVICTED = "serve.evicted"
#: requests served to completion
SERVE_COMPLETED = "serve.completed"
#: ``StencilServer.drain`` runs that hit the ``max_cycles`` bound with
#: work still queued (no-silent-caps: the truncation also logs the bound
#: and the remaining depth)
SERVE_DRAIN_TRUNCATED = "serve.drain.truncated"
#: packed dispatches (batched group or sub-slice placement) that fell
#: back to serial re-execution after a classified failure
SERVE_BATCH_FALLBACKS = "serve.batch.fallbacks"
#: successful BATCHED dispatches (always-live engagement evidence: the
#: soak's packed legs assert > 0 — histograms only record with telemetry
#: enabled, and digest equality alone cannot prove batching ran)
SERVE_BATCH_DISPATCHES = "serve.batch.dispatches"
#: successful sub-slice packed cycles (same role for the bin-packer)
SERVE_SUBSLICE_DISPATCHES = "serve.subslice.dispatches"
#: bytes received over ONE mesh hop, summed over subdomains — one counter per
#: (axis, direction) so the comms roofline can price each link of the
#: realized mesh: the messages of the plan that is RUN (``ops/exchange.
#: exchange_account``, read off ``_sweep_kind``; a packed sweep counts its
#: padded buffer) — for ``exchange()`` every quantity at the shell radius
#: (``DistributedDomain.exchange_hop_bytes``), for a ``run_step`` dispatch the
#: quantities, axes, radius and exchanges a step its builder declared
#: (``WireAccount``); held hop by hop to the ``ppermute`` operands of the
#: traced programs by ``tests/test_wire_account.py``; 0 on axes the mesh does
#: not split
EXCHANGE_HOP_X_LOW_BYTES = "exchange.hop.x.low.bytes"
EXCHANGE_HOP_X_HIGH_BYTES = "exchange.hop.x.high.bytes"
EXCHANGE_HOP_Y_LOW_BYTES = "exchange.hop.y.low.bytes"
EXCHANGE_HOP_Y_HIGH_BYTES = "exchange.hop.y.high.bytes"
EXCHANGE_HOP_Z_LOW_BYTES = "exchange.hop.z.low.bytes"
EXCHANGE_HOP_Z_HIGH_BYTES = "exchange.hop.z.high.bytes"
#: point-to-point fabric-probe transfers actually measured on device
#: (telemetry/fabric.py — 0 when the probe answered from its warm cache)
FABRIC_PROBE_RUNS = "fabric.probe.runs"
#: fabric-probe cache consultations that found a persisted link matrix
FABRIC_CACHE_HIT = "fabric.cache.hit"
#: consultations that found nothing (cold cache, stale schema/toolchain,
#: corrupt artifact) — mirrors the tune-cache miss semantics
FABRIC_CACHE_MISS = "fabric.cache.miss"

#: the per-hop byte counter for one (mesh axis, direction) — direction names
#: follow the receive side: ``low`` receives from the -1 neighbor
EXCHANGE_HOP_BYTES = {
    ("x", "low"): EXCHANGE_HOP_X_LOW_BYTES,
    ("x", "high"): EXCHANGE_HOP_X_HIGH_BYTES,
    ("y", "low"): EXCHANGE_HOP_Y_LOW_BYTES,
    ("y", "high"): EXCHANGE_HOP_Y_HIGH_BYTES,
    ("z", "low"): EXCHANGE_HOP_Z_LOW_BYTES,
    ("z", "high"): EXCHANGE_HOP_Z_HIGH_BYTES,
}

# --- set-up accounting (always live; docs/observability.md "Set-up") ---------
#
# A span opened with ``telemetry.span(..., total=PHASE_*)`` is a PHASE of the
# program's start: it goes on a per-thread phase stack, and a timed phase adds
# its wall time and a count to the always-live totals below -- recorder on or
# off, profiler session or none.  jax's own monitoring events (trace, lower,
# backend compile, persistent-cache traffic; ``telemetry.watch_jax``) are
# folded into counters keyed by the INNERMOST open phase.  Every series is
# ``<epoch>.<what>.<phase>``: epoch ``setup`` until the first steady dispatch
# of any program, ``run`` from then on.

#: ``domain.realize``: allocation, the exchange's build and eager compile
PHASE_REALIZE = "realize"
#: ``domain.init``: one seeded fill, its jit included
PHASE_INIT = "init"
#: ``domain.compile``: an eager compile (exchange, ladder rung, serving AOT)
PHASE_COMPILE = "compile"
#: the first call of a program with given static arguments (``domain.step`` /
#: ``domain.exchange`` carrying ``first=1``): jax traces, lowers and compiles
#: or loads inside it
PHASE_FIRST_DISPATCH = "first_dispatch"
#: every later call of that program: on the phase stack so that a recompile
#: is charged to it, but NOT timed -- no clock read on the steady path
PHASE_STEADY = "steady"
#: no program span open on this thread (a caller's own jits)
PHASE_OUTSIDE = "outside"

#: the phases a span's wall time is added up for (spans nest -- the eager
#: exchange compile sits inside ``realize`` -- and each total is inclusive)
TIMED_PHASES = (PHASE_REALIZE, PHASE_INIT, PHASE_COMPILE, PHASE_FIRST_DISPATCH)
PHASES = TIMED_PHASES + (PHASE_STEADY, PHASE_OUTSIDE)

EPOCH_SETUP = "setup"
EPOCH_RUN = "run"
EPOCHS = (EPOCH_SETUP, EPOCH_RUN)

#: wall seconds / number of the spans opened under a timed phase
TOTAL_SPAN_SECONDS = "span_seconds"
TOTAL_SPAN_COUNT = "span_count"
#: Python tracing + lowering to MLIR (paid on every start, warm or cold);
#: nested traces (a jit traced inside a jit) count once, at the outermost
TOTAL_TRACE_SECONDS = "trace_seconds"
#: backend compile -- in jax 0.9 the event wraps ``compile_or_get_cached``,
#: so a persistent-cache hit's retrieval is inside it -- and how many
TOTAL_BACKEND_SECONDS = "backend_seconds"
TOTAL_BACKEND_COMPILES = "backend_compiles"
#: reading an executable back from the persistent cache (already inside
#: ``backend_seconds``: kept apart, never added to it)
TOTAL_CACHE_RETRIEVAL_SECONDS = "cache_retrieval_seconds"
TOTAL_CACHE_HITS = "cache_hits"
TOTAL_CACHE_MISSES = "cache_misses"

#: the two of jax's ``jax.monitoring`` events the listener treats specially:
#: traces nest, and a backend compile is also counted
JAX_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
JAX_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: jax's duration events -> the total each is added to
JAX_DURATION_TOTALS = {
    JAX_TRACE_EVENT: TOTAL_TRACE_SECONDS,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": TOTAL_TRACE_SECONDS,
    JAX_BACKEND_COMPILE_EVENT: TOTAL_BACKEND_SECONDS,
    "/jax/compilation_cache/cache_retrieval_time_sec": TOTAL_CACHE_RETRIEVAL_SECONDS,
}
#: jax's plain events -> the total each counts in
JAX_EVENT_TOTALS = {
    "/jax/compilation_cache/cache_hits": TOTAL_CACHE_HITS,
    "/jax/compilation_cache/cache_misses": TOTAL_CACHE_MISSES,
}

#: (epoch, total, phase) -> the counter's name: span totals for the timed
#: phases, jax's for every phase
PHASE_SERIES = {
    (epoch, total, phase): f"{epoch}.{total}.{phase}"
    for epoch in EPOCHS
    for total, phases in (
        [(t, TIMED_PHASES) for t in (TOTAL_SPAN_SECONDS, TOTAL_SPAN_COUNT)]
        + [
            (t, PHASES)
            for t in (TOTAL_BACKEND_COMPILES, *JAX_DURATION_TOTALS.values(),
                      *JAX_EVENT_TOTALS.values())
        ]
    )
    for phase in phases
}


ALL_COUNTERS = frozenset({
    EXCHANGE_COUNT,
    EXCHANGE_BYTES,
    EXCHANGE_PACKED_BYTES,
    EXCHANGE_PACKED_KERNELS,
    EXCHANGE_JOINT_SWEEPS,
    STEP_DISPATCHES,
    STEP_ITERATIONS,
    RETRY_ATTEMPTS,
    RETRY_EXHAUSTED,
    RETRY_REFUSED,
    LADDER_DESCENTS,
    FAULTS_INJECTED,
    SENTINEL_TRIPS,
    TUNE_CACHE_HIT,
    TUNE_CACHE_MISS,
    TUNE_TRIALS,
    TUNE_PRUNED,
    TUNE_SELECTED,
    STEP_OVERLAP_EXTERIOR_CELLS,
    CHECKPOINT_SAVES,
    CHECKPOINT_SAVE_BYTES,
    CHECKPOINT_RESTORES,
    CHECKPOINT_INVALID,
    SUPERVISOR_RESTARTS,
    WATCHDOG_STALLS,
    PROFILE_CAPTURES,
    RESHARDS,
    RESHARD_BYTES,
    RESHARD_FALLBACKS,
    NUMERICS_SNAPSHOTS,
    NUMERICS_DRIFT,
    SERVE_ADMITTED,
    SERVE_REJECTED,
    SERVE_SHED,
    SERVE_EVICTED,
    SERVE_COMPLETED,
    SERVE_DRAIN_TRUNCATED,
    SERVE_BATCH_FALLBACKS,
    SERVE_BATCH_DISPATCHES,
    SERVE_SUBSLICE_DISPATCHES,
    EXCHANGE_HOP_X_LOW_BYTES,
    EXCHANGE_HOP_X_HIGH_BYTES,
    EXCHANGE_HOP_Y_LOW_BYTES,
    EXCHANGE_HOP_Y_HIGH_BYTES,
    EXCHANGE_HOP_Z_LOW_BYTES,
    EXCHANGE_HOP_Z_HIGH_BYTES,
    FABRIC_PROBE_RUNS,
    FABRIC_CACHE_HIT,
    FABRIC_CACHE_MISS,
    *PHASE_SERIES.values(),
})

# --- gauges (last-value) -----------------------------------------------------

#: analytic bytes per single exchange across all subdomains
EXCHANGE_BYTES_PER_EXCHANGE = "domain.exchange.bytes_per_exchange"
#: checkpoints currently RETAINED in the ring after pruning (last value of
#: ``keep``-bounded ring size — io/checkpoint.py ``save_to_ring``)
CHECKPOINT_RETAINED = "checkpoint.retained"

#: serving request-queue depth after each admission/dispatch (the signal
#: the elasticity policy watches)
SERVE_QUEUE_DEPTH = "serve.queue.depth"
#: tenants currently in the "active" state (admitted, not quarantined)
SERVE_TENANTS_ACTIVE = "serve.tenants.active"
#: fraction of the fleet's devices busy in the most recent dispatch
#: (1.0 = a full-fleet or batched dispatch; a sub-slice pack sums its
#: disjoint slices — the throughput scheduler's utilization signal)
SERVE_OCCUPANCY = "serve.occupancy"

ALL_GAUGES = frozenset({
    EXCHANGE_BYTES_PER_EXCHANGE,
    CHECKPOINT_RETAINED,
    SERVE_QUEUE_DEPTH,
    SERVE_TENANTS_ACTIVE,
    SERVE_OCCUPANCY,
})

# --- histograms (Statistics-backed: min/max/avg/stddev/med/trimean) ----------

#: wall seconds per RAW iteration through ``run_step`` (dispatch time / raw
#: steps, honest-synced)
STEP_SECONDS = "domain.step.seconds"
#: wall seconds per direct ``exchange()`` call (honest-synced)
EXCHANGE_SECONDS = "domain.exchange.seconds"
#: wall seconds per ``swap()`` call
SWAP_SECONDS = "domain.swap.seconds"
#: degradation-ladder rung build (trace/compile) seconds
LADDER_BUILD_SECONDS = "resilience.ladder.build_seconds"
#: wall seconds per checkpoint commit (gather + write + fsync + rename)
CHECKPOINT_SAVE_SECONDS = "checkpoint.save.seconds"
#: wall seconds per checkpoint restore (load + verify + re-scatter)
CHECKPOINT_RESTORE_SECONDS = "checkpoint.restore.seconds"
#: wall seconds per in-memory mesh transition (plan + collective schedule
#: + exchange re-realize + tuner re-key — ``DistributedDomain.reshard``)
RESHARD_SECONDS = "reshard.seconds"
#: wall seconds per fused numerics snapshot (dispatch + the scalar
#: readback — the "cheap enough to leave on" figure bench.py's
#: numerics_overhead A/B regression-gates)
NUMERICS_SNAPSHOT_SECONDS = "numerics.snapshot.seconds"
#: end-to-end wall seconds per served request (enqueue -> response; the
#: fleet-wide series — per-tenant p50/p95/p99 live in each tenant's own
#: Statistics and surface through the heartbeat tenant table)
SERVE_LATENCY_SECONDS = "serve.latency.seconds"
#: wall seconds per AOT executable compile at admission (serve/aot.py —
#: the cost the admission budget bounds)
SERVE_COMPILE_SECONDS = "serve.compile.seconds"
#: requests carried per BATCHED dispatch (serve/pack.py — geometry-matched
#: groups stacked along a leading batch axis into one dispatch)
SERVE_BATCH_SIZE = "serve.batch.size"
#: tenants packed per sub-slice dispatch cycle (disjoint sub-meshes of
#: the fleet executing concurrently)
SERVE_SUBSLICE_COUNT = "serve.subslice.count"
#: measured point-to-point link bandwidth over the realized mesh, GB/s per
#: probed neighbor edge (telemetry/fabric.py — the NVML-distance-matrix
#: analog feeding the comms roofline)
FABRIC_LINK_GBPS = "fabric.link.gbps"
#: wall seconds per fabric-probe sweep (warm-up + all measured rounds)
FABRIC_PROBE_SECONDS = "fabric.probe.seconds"

ALL_HISTOGRAMS = frozenset({
    STEP_SECONDS,
    EXCHANGE_SECONDS,
    SWAP_SECONDS,
    LADDER_BUILD_SECONDS,
    CHECKPOINT_SAVE_SECONDS,
    CHECKPOINT_RESTORE_SECONDS,
    RESHARD_SECONDS,
    NUMERICS_SNAPSHOT_SECONDS,
    SERVE_LATENCY_SECONDS,
    SERVE_COMPILE_SECONDS,
    SERVE_BATCH_SIZE,
    SERVE_SUBSLICE_COUNT,
    FABRIC_LINK_GBPS,
    FABRIC_PROBE_SECONDS,
})

# --- spans (Chrome-trace timeline entries) -----------------------------------

# Host spans (``telemetry.span``) go two ways: into the perf_counter
# recorder when STENCIL_TELEMETRY is on, and ALWAYS into the profiler's own
# trace as a ``jax.profiler.TraceAnnotation`` of the same name -- under a
# profiler session they sit on /host:CPU of the same xplane as the device
# ops (docs/observability.md "One timeline").  Args in brackets.  Three
# events double as the span around the work they report (one constant, one
# name, like NUMERICS_DRIFT): EVENT_COMPILE [label], EVENT_RETRY (the
# back-off sleep) [label, attempt], EVENT_CHECKPOINT_SAVE [step].

#: the enqueue of one ``run_step`` dispatch [label, steps = raw iterations;
#: a stream-engine step adds the plan it ran: route, x_radius, grouping,
#: streamed = quantities in the pass, aliased = quantities the passes carry in
#: place (all or none: ``ops/stream_plan._plan_passes_in_place``; a written one's
#: output aliases its input, an unwritten one is its input), exchanged
#: = quantities riding the step's halo exchange: on the plane route those the
#: kernel reads off-centre (``ops/stream_plan.trace_plane_kernel``; all of them under
#: ``halo="fused"``), every one on the wavefront route, 0 on the wrap route,
#: written = quantities that are outputs of the passes: on the plane route
#: those the kernel returns (the same trace, ``plan["writers"]``; all of them
#: under ``halo="fused"``), every one on the other routes, renamed = the
#: quantities whose write became a rename: an output that IS another writer's
#: centre plane (a leapfrog's ``u_prev <- u``) swaps handles with it and is
#: not ``written`` (``ops/stream_plan.trace_plane_kernel``, ``plan["renamed"]``:
#: acoustic 1; 0 wherever the passes do not run in place on the plane route's
#: default schedule), wrapped = the axes
#: whose halo the plane passes fill themselves in VMEM, so that the step's
#: exchange does not sweep them (``ops/stream_plan.pass_wrap_fills``: the y / z
#: axes the mesh does not split, "yz" on one chip, "z" on mesh [2,2,1], ""
#: off the plane route's default schedule and wherever that axis's sweep is
#: not the self-wrap), wired = the axes over which the step's exchanges send
#: to ANOTHER shard and wire_bytes = the bytes one shard receives over them a
#: RAW step, every stage -- the step's ``WireAccount``, which ``run_step``
#: counts ``exchange.hop.*.bytes`` from (``ops/exchange.exchange_account``, read off
#: what ``_sweep_kind`` decides: acoustic on mesh [2,2,1] "xy" and 23658496 =
#: four radius-4 faces of ``u``'s 608^3 block, "" and 0 on one device; a
#: wavefront's macro bytes over its depth; the bespoke ``Jacobi3D`` steps say
#: both too), joint = the pair of wired axes whose sweeps of those exchanges
#: fly jointly -- both axes' faces sent at once, the corner strips relayed
#: behind them (``ops/exchange._sweep_groups``): "xy" on mesh [2,2,1], "" where
#: the sweeps run in turn (fewer than two wired ``direct`` sweeps in a row: one
#: device, [2,1,1], [2,1,2], the packed kinds); a STAGED step (``make_step``
#: with a sequence of kernels) adds stages and passes, and says exchanged /
#: written / renamed / aliased PER STAGE, in order: "6/3", "3/6", "0/0",
#: "11/12" (``wrapped``, ``wired`` and ``wire_bytes`` are one value each: the
#: first two functions of the mesh, the last summed over the stages) and, on
#: the plane route's swept exchange, wire_bytes_by_stage = those bytes stage by stage
#: ("26359296/26359296/26359296" for Astaroth's MHD step on mesh [2,2,1]: three
#: exchanges of the same eight fields; a stage that exchanges nothing 0); a step
#: with a stage of SEVERAL passes adds passes_by_stage = every pass in the order
#: its stage runs it -- the quantities it writes, reads and holds a ring of, the
#: rows of its y tiles and how many a plane is (0 and 1: whole planes), its
#: writes that land in another quantity's block --, "w2-r10-g8-t256-y2-n2", the
#: passes of a stage "+"-joined and the stages "/"-joined
#: (``ops/stream.stream_span_args``; ``astaroth-mhd-512.bulk``: four passes a
#: substep, ``tile_rows`` / ``y_tiles`` beside it the step's smallest tile); every
#: plane step on that schedule says wired_edges = the pairs of wired axes its
#: kernels read DIAGONALLY across (``ops/stream_plan.edge_reads`` over
#: ``PlaneTrace.offsets``, "/"-joined): that EDGE halo is the diagonal
#: neighbour's and reaches the shard over two wires in turn, the later sweep
#: carrying what the earlier one received -- "xy" for the MHD step's mixed
#: differences on [2,2,1], "" on one device, on [2,1,1] and for a star; a
#: ``Jacobi3D`` wrap or wavefront step adds macros_per_trip = the macros one
#: trip of its device-side macro loop runs, as many as it takes for the carry
#: to be back in its own buffer (``ops/stream.macro_loop``): 2 where the
#: kernel writes a fresh result, 1 where it writes in place (``alias``) -- and
#: so does a stream-engine step on the wrap route (2: ``stream_wrap_pass``
#: writes fresh results), which says beside it edges = where a dispatch's two
#: edges live: "raw" = its first pass reads the domain's raw blocks and its
#: last one writes them, in place (the pass's edge forms), "xla" = a
#: ``lax.slice`` and a ``dynamic_update_slice`` a quantity around the passes --
#: read off the block's static shape and the VMEM model AFTER the depth is
#: chosen (``ops/stream_plan.wrap_edge_form``), "raw" in ``lbm-d3q19-256.bulk``;
#: a dispatch of ONE call keeps the cut and the write-back whatever it says; a
#: stream-engine step on the PLANE route says
#: steps_per_trip = the steps one trip of its step loop runs, as many as bring
#: the handles its renames swap back to their own buffers
#: (``ops/stream_plan._carry_period``): 1 with no rename (elastic), 2 for
#: acoustic's one swap a step and for Astaroth's MHD step, whose three stages
#: each swap all eight ``(q_prev, q)`` pairs (``renamed`` "8/8/8", an odd
#: count of swaps a step; dispatch a multiple of it), and plane_window = the
#: plane its passes work on: "interior" where they make BOTH in-plane halo
#: fills themselves (``wrapped`` "yz"), each the self-wrap of the block's whole
#: interior, and that interior is whole vector tiles -- the working plane is
#: the block's aligned corner, the interior rotated by the low shell widths,
#: every in-plane shift is one native rotate whose wraparound is the halo
#: --, "interior-z" where they make the z fill alone (``wrapped`` "z": the
#: mesh splits y) beside an interior of whole vector tiles -- the z halo is the
#: lane rotates' wraparound as above, and the neighbours' y halo rows ride in
#: the margin tiles of the strip form's tile layout, no plane cut at an
#: unaligned row --, "raw" = the shell-carrying plane, everywhere
#: else: a z the mesh splits, ragged lanes or rows, a light kernel beside a
#: split y whose whole raw planes fit a pass (``ops/stream_pass.
#: plane_window_form``, read off the fills and the block's static shape:
#: "interior" in ``astaroth-mhd-256.bulk`` and ``lbm-d3q19-512.bulk``,
#: "interior-z" in ``astaroth-mhd-256x4.bulk`` and ``lbm-d3q19-512x4.bulk``,
#: "raw" in the three 600-extent plane cells)
#: and plane_strip = the rows ``S`` of that plane
#: its passes evaluate their kernel over at a time -- a loop over the plane's
#: strips inside a grid step, the planes held as tiles whose next row is the
#: next tile (a y shift an address and no rotate), a value of the kernel ``S /
#: 8 x Zw / 128`` vregs and not a whole plane's -- on the two aligned windows
#: and for a kernel of ``_STRIP_MIN_OPS`` or more operations a cell -- or a
#: lighter one whose whole planes fit no pass, so that it moves y tiles --, 0 =
#: the kernel runs over the plane whole (``ops/stream_pass.plane_strip_rows``
#: and ``ops/stream_plan.plan_plane_stages``, read off the window, the plane
#: and the kernels' traces: 16 in both ``astaroth-mhd-256`` cells, 8 in both
#: ``lbm-d3q19-512`` cells, 0 in the three 600-extent plane cells) and
#: tile_rows / y_tiles = the rows of the Y TILES its pipeline moves of a plane,
#: and how many a plane is, where a pass that cannot be cut further fits VMEM
#: with whole planes in no form -- the planes the kernel reads are then whole
#: in VMEM scratch only, ``ops/stream_pass.stream_plane_pass_tiled``, on either
#: aligned window (beside a split y the two ends of a plane's tiles are the
#: block's own halo rows, a neighbour's cells, and no wrap); 0 and 1 = the
#: passes move whole planes, every cell but ``lbm-d3q19-512.bulk`` and
#: ``lbm-d3q19-512x4.bulk`` (128 and 4; ``ops/stream_plan.plan_plane_passes``,
#: read off the one VMEM model) and plane_lanes = the lane tiles of a raw plane
#: the passes move on the side that faces another call of the dispatch:
#: "window" = the aligned window's alone -- the dispatch's first call reads
#: whole raw planes and makes the fills but writes ``(1, Yt, Zw)`` blocks, the
#: calls between read and write such blocks alone, and the last reads them and
#: writes whole planes with the z shell rebuilt (a dispatch of ONE step is one
#: whole call, of two the two edge calls) --, "raw" = whole raw planes both
#: ways every call: every cell but the two ``lbm-d3q19-512`` ones
#: (``ops/stream_plan.plane_lanes_form``, read off the resolved plan: one tiled
#: pass a step, in place, that writes every quantity it reads), and beside
#: "window" narrow_calls = the calls of THIS dispatch that moved the window's
#: lane tiles both ways, ``steps - 2`` and 0 for a dispatch of one or two
#: (``ops/stream.stream_dispatch_args``, off the list the dispatch runs,
#: ``ops/stream_plan.plane_lane_forms``; no "raw" span carries it); a
#: z-slab wavefront step (``Jacobi3D``'s z-ring and
#: lane-padded shell kernels, the stream engine's wavefront route with
#: ``z_slabs``) adds z_halo_patch = where its kernel patches the z halo into
#: the working plane: "tile" = inside the 128-lane tiles that hold the halo
#: lanes, on a plane that is whole lane tiles, "plane" = compare + select over
#: the whole plane, on one that is not (``ops/jacobi_pallas.patch_z_halo``,
#: read off the static shapes by ``z_halo_patch_form``: "tile" in
#: ``jacobi3d-512x4.bulk`` and ``astaroth-8q-512.bulk``; no other step says
#: it), and the stream engine's z-slab wavefront step adds lane_pad = where
#: the working plane's lane padding lives: "vmem" = the step carries the
#: domain's raw blocks and ``stream_wavefront_pass`` widens each plane to whole
#: lane tiles itself, through a boundary block (``astaroth-8q-512.bulk``: 518
#: lanes in a 640-lane plane), "none" = the raw z extent is a multiple of 128
#: already (read off ``Zr % 128``; the step pads and cuts nothing in HBM
#: either way); every z-slab wavefront step says slab_wrap = the axes on which
#: a macro's slab extension sends nothing to itself -- on z the outgoing slab
#: buffer is the incoming one, on y and x the self-wrap kernel fills the
#: buffer's shell in place, no ``ppermute`` to oneself and no
#: ``dynamic_update_slice`` (``ops/exchange.slab_wrap_axes``, ``_sweep_kind``'s
#: rule read off the mesh, the dtypes and the backend, as ``wrapped`` is: "xyz"
#: in ``astaroth-8q-512.bulk``, "z" on mesh [2,2,1], "" wherever the blend
#: kernels cannot engage, the CPU's default among them; the axes ``wired``
#: names are never in it); every stream-engine step says what its kernels READ
#: beside what the route SERVES: quantities = the quantities it carries,
#: offcentre = those read at a non-zero offset, diagonal = those of them read
#: at an offset with two or more non-zero components (an edge or corner halo),
#: read_sides = the distinct (quantity, axis, side) triples read
#: (``ops/stream_plan.footprint_counts`` over one abstract trace of each kernel;
#: None each where it raised) and exchanged_sides = six for every quantity
#: whose halo the route fills (D3Q19 lattice Boltzmann: 19, 18, 12, 30 and 0 on
#: the wrap route, 108 on the plane route)]
SPAN_STEP = "domain.step"
#: one ``exchange()`` / ``exchange_many()`` call [route, nbytes = analytic
#: bytes of the call, count = exchanges in it, wrap_axes = the mesh axes
#: whose sweep is the self-wrap kernel, e.g. "z" on mesh [2,2,1], uneven_axes
#: = the mesh axes swept at per-shard traced offsets because the mesh does
#: not divide the extent (``ops/exchange.uneven_axes``), e.g. "xy" for 1191^3
#: on that mesh, "" on every aligned extent, wire_bytes = the bytes one shard
#: receives over wires per exchange (``domain.step``'s meaning; the sum of
#: ``exchange_hop_bytes`` a subdomain), joint = the axes whose sweeps fly
#: jointly (``domain.step``'s meaning)]
SPAN_EXCHANGE = "domain.exchange"
SPAN_SWAP = "domain.swap"
#: ``realize()`` once the geometry is known: allocation, exchange build +
#: eager compile [valid_last = the last shard's valid cells per axis, "-" where
#: the mesh divides the extent, e.g. "595,595,-"]
SPAN_REALIZE = "domain.realize"
#: ``init_by_coords``: builds and traces a new jit per call [quantity]
SPAN_INIT = "domain.init"
#: one fused numerics snapshot's dispatch + scalar read-back (the divergence
#: sentinel's check too): host work that drains the dispatch queue [step]
SPAN_NUMERICS_SNAPSHOT = "numerics.snapshot"
#: the split-step schedule's two halves (ops/stream.py overlap=split).  These
#: are DEVICE-timeline spans: the split macro enters them as
#: ``telemetry.annotate`` named scopes, so they label the interior stream
#: pass / exterior band passes in compiled HLO metadata and XProf profiles —
#: the tier-1/tier-2 overlap proofs key on the interior scope name.
SPAN_OVERLAP_INTERIOR = "step.overlap.interior"
SPAN_OVERLAP_EXTERIOR = "step.overlap.exterior"
#: the plane route's streaming pass (ops/stream.py), a DEVICE-timeline scope
#: like the two above: the pass and the copies the compiler adds to feed it
#: carry it, so a trace splits a plane step into pass, exchange sweeps
#: (``exchange.<axis>``) and whatever is left
SPAN_STEP_PASS = "step.pass"
#: one STAGE of a plane-route step of several (``make_step`` with a sequence
#: of kernels: elastic's velocities, then its stresses): a DEVICE-timeline
#: scope around the stage's exchange sweeps and its passes, ``step.stage.0``,
#: ``step.stage.1``, ... (``step_stage_span``); a one-stage step has none
SPAN_STEP_STAGE = "step.stage"
#: one PASS of a stage that runs several and hands its blocks on renamed
#: (Astaroth's MHD substep at 512^3: four passes, eight renames): a
#: DEVICE-timeline scope below the stage's, around the pass's own ``step.pass``,
#: ``step.stage.<k>/pass.0``, ``.../pass.1``, ... in the order the stage runs them
#: (``stage_pass_span``), so that a trace splits a stage by pass; a stage of one
#: pass, and one that renames nothing, has none
SPAN_STAGE_PASS = "pass"
#: the redistribution collective schedule (parallel/redistribute.py): a
#: named scope entered around the per-round slice/permute/blend body, so
#: device-time attribution can price a live mesh transition
SPAN_RESHARD = "reshard.collective"
#: the halo-exchange ppermutes, one DEVICE-timeline scope per (mesh axis,
#: receive direction) — ops/exchange.py enters these around every
#: ``lax.ppermute`` so profiler traces attribute collective-permute device
#: time per link (``exchange.z.low`` receives the -1 z-neighbor's shell)
SPAN_EXCHANGE_X_LOW = "exchange.x.low"
SPAN_EXCHANGE_X_HIGH = "exchange.x.high"
SPAN_EXCHANGE_Y_LOW = "exchange.y.low"
SPAN_EXCHANGE_Y_HIGH = "exchange.y.high"
SPAN_EXCHANGE_Z_LOW = "exchange.z.low"
SPAN_EXCHANGE_Z_HIGH = "exchange.z.high"
#: the SELF-WRAP of an axis the mesh does not split: no wire, one in-place
#: kernel per quantity filling both halos from the shard's own interior
#: (ops/halo_blend.py ``wrap_halo``) — the scope that tells those kernels
#: from the blends of a received slab, whose registered names they share
SPAN_EXCHANGE_X_WRAP = "exchange.x.wrap"
SPAN_EXCHANGE_Y_WRAP = "exchange.y.wrap"
SPAN_EXCHANGE_Z_WRAP = "exchange.z.wrap"

#: one axis SWEEP of the halo exchange -- slab cut / pack, the wire, unpack /
#: blend -- as a DEVICE-timeline scope: every instruction the exchange adds
#: to a program sits under one of these (the direction scopes above nest
#: inside), so a trace reader tells exchange work from step glue by name
SPAN_EXCHANGE_X = "exchange.x"
SPAN_EXCHANGE_Y = "exchange.y"
SPAN_EXCHANGE_Z = "exchange.z"

#: the sweep scope for one mesh axis
EXCHANGE_AXIS_SPANS = {
    "x": SPAN_EXCHANGE_X,
    "y": SPAN_EXCHANGE_Y,
    "z": SPAN_EXCHANGE_Z,
}

#: the direction span for one (mesh axis, receive side)
EXCHANGE_DIRECTION_SPANS = {
    ("x", "low"): SPAN_EXCHANGE_X_LOW,
    ("x", "high"): SPAN_EXCHANGE_X_HIGH,
    ("y", "low"): SPAN_EXCHANGE_Y_LOW,
    ("y", "high"): SPAN_EXCHANGE_Y_HIGH,
    ("z", "low"): SPAN_EXCHANGE_Z_LOW,
    ("z", "high"): SPAN_EXCHANGE_Z_HIGH,
}

#: the self-wrap scope for one mesh axis
EXCHANGE_WRAP_SPANS = {
    "x": SPAN_EXCHANGE_X_WRAP,
    "y": SPAN_EXCHANGE_Y_WRAP,
    "z": SPAN_EXCHANGE_Z_WRAP,
}


def step_stage_span(k: int) -> str:
    """The device scope of stage ``k`` of a staged step."""
    return f"{SPAN_STEP_STAGE}.{int(k)}"


def stage_pass_span(i: int) -> str:
    """The device scope of pass ``i`` of a stage of several renaming passes."""
    return f"{SPAN_STAGE_PASS}.{int(i)}"


def exchange_direction_span(axis: str, side: str) -> str:
    """The registered span name for one exchange hop (axis in x/y/z, side in
    low/high).  In-kernel scopes must come through here (or the constants
    above) so the span registry stays the single name authority."""
    try:
        return EXCHANGE_DIRECTION_SPANS[(axis, side)]
    except KeyError:
        raise ValueError(f"no exchange direction span for {axis!r}/{side!r}") from None


def exchange_axis_span(axis: str) -> str:
    """The registered sweep scope for one mesh axis (x/y/z)."""
    try:
        return EXCHANGE_AXIS_SPANS[axis]
    except KeyError:
        raise ValueError(f"no exchange sweep span for axis {axis!r}") from None


def exchange_wrap_span(axis: str) -> str:
    """The registered self-wrap scope for one mesh axis (x/y/z)."""
    try:
        return EXCHANGE_WRAP_SPANS[axis]
    except KeyError:
        raise ValueError(f"no exchange self-wrap span for axis {axis!r}") from None


ALL_SPANS = frozenset({
    SPAN_STEP,
    SPAN_EXCHANGE,
    SPAN_SWAP,
    SPAN_REALIZE,
    SPAN_INIT,
    SPAN_NUMERICS_SNAPSHOT,
    SPAN_EXCHANGE_X,
    SPAN_EXCHANGE_Y,
    SPAN_EXCHANGE_Z,
    SPAN_OVERLAP_INTERIOR,
    SPAN_OVERLAP_EXTERIOR,
    SPAN_STEP_PASS,
    SPAN_STEP_STAGE,
    SPAN_STAGE_PASS,
    SPAN_RESHARD,
    SPAN_EXCHANGE_X_LOW,
    SPAN_EXCHANGE_X_HIGH,
    SPAN_EXCHANGE_Y_LOW,
    SPAN_EXCHANGE_Y_HIGH,
    SPAN_EXCHANGE_Z_LOW,
    SPAN_EXCHANGE_Z_HIGH,
    SPAN_EXCHANGE_X_WRAP,
    SPAN_EXCHANGE_Y_WRAP,
    SPAN_EXCHANGE_Z_WRAP,
})

# --- structured events (JSONL sink) ------------------------------------------

#: a compile happened (fields: phase, label, seconds)
EVENT_COMPILE = "domain.compile"
#: a transient failure is being retried (fields: label, attempt,
#: max_retries, delay_s, error)
EVENT_RETRY = "resilience.retry"
#: the retry budget ran out (fields: label, max_retries, error)
EVENT_RETRY_EXHAUSTED = "resilience.retry_exhausted"
#: a retry was refused by the donated-buffer guard (fields: label, error)
EVENT_RETRY_REFUSED = "resilience.retry_refused"
#: a ladder descent (fields: label, from_rung, to_rung, failure_class)
EVENT_DESCENT = "resilience.descent"
#: a STENCIL_FAULT_PLAN fault fired (fields: phase, label, failure_class)
EVENT_FAULT = "resilience.fault_injected"
#: the divergence sentinel tripped (fields: quantity, step, window =
#: [last clean check, detection step], coord = global first-non-finite
#: cell or null — telemetry/numerics.py feeds all three on-device)
EVENT_DIVERGENCE = "resilience.divergence"
#: a tuning decision (fields: key, source=cache|search|static, config,
#: trials, pruned)
EVENT_TUNE_DECISION = "tune.decision"
#: one autotuner trial finished (fields: key, candidate, seconds_per_iter —
#: or failure_class/error when the candidate was pruned)
EVENT_TUNE_TRIAL = "tune.trial"
#: the exchange planner resolved its z-sweep route (fields: route,
#: source=explicit|env|tuned|static|ladder — or "<orig>/degraded" when a
#: packed pick structurally could not engage —, wrap_axes = the mesh axes
#: whose sweep under that route is the self-wrap kernel, "" when none)
EVENT_EXCHANGE_ROUTE = "exchange.route"
#: a stream-engine step build resolved its overlap schedule (fields:
#: overlap=off|split, source=explicit|env|tuned|static|ladder or
#: "<orig>/degraded" on a structural step-down, route, m)
EVENT_STEP_OVERLAP = "step.overlap"
#: a stream-engine step build resolved its halo consumption mode (fields:
#: halo=array|fused, source=explicit|env|tuned|static|ladder or
#: "<orig>/degraded" on a structural step-down, route, m, exchange_route)
EVENT_STEP_HALO = "step.halo"
#: a model build resolved its storage-dtype axis (fields:
#: storage=native|bf16, source=explicit|env|tuned|static or
#: "<orig>/degraded" on a structural step-down, where)
EVENT_KERNEL_STORAGE_DTYPE = "kernel.storage_dtype"
#: a checkpoint committed (fields: path, step, backend, bytes, seconds,
#: reason=cadence|final|preempt)
EVENT_CHECKPOINT_SAVE = "checkpoint.save"
#: a checkpoint restored (fields: path, step, backend, elastic, seconds)
EVENT_CHECKPOINT_RESTORE = "checkpoint.restore"
#: a checkpoint failed validation and the ring fell back past it (fields:
#: path, why)
EVENT_CHECKPOINT_FALLBACK = "checkpoint.fallback"
#: the supervisor restarted from the last valid checkpoint (fields: label,
#: step, restart, budget, failure_class, error)
EVENT_SUPERVISOR_RESTART = "supervisor.restart"
#: the watchdog saw a dispatch exceed its deadline (fields: phase,
#: deadline_s, abort)
EVENT_WATCHDOG_STALL = "watchdog.stall"
#: a cadence device-profile capture finished (fields: dir, index,
#: seconds — telemetry/device.py)
EVENT_PROFILE_CAPTURE = "profile.capture"
#: an in-memory mesh transition completed (fields: from_mesh, to_mesh,
#: seconds, bytes, quantities, source=request|capacity_loss|operator)
EVENT_RESHARD = "reshard.transition"
#: a capacity change fell back to checkpoint-elastic-restore (fields:
#: from_mesh, to_mesh, why, step) — charged against the restart budget
EVENT_RESHARD_FALLBACK = "reshard.fallback"
#: sustained healthy progress restored one restart credit (fields: label,
#: step, window, credits_used — STENCIL_RESTART_WINDOW)
EVENT_SUPERVISOR_REPLENISH = "supervisor.replenish"
#: an admission decision (fields: tenant, admitted, why, queue_depth,
#: compile_s when a cold key compiled at admission)
EVENT_SERVE_ADMISSION = "serve.admission"
#: queued load was shed (fields: tenant, why=deadline|priority|injected,
#: queue_depth, waited_s)
EVENT_SERVE_SHED = "serve.load_shed"
#: the per-tenant envelope quarantined/evicted a tenant (fields: tenant,
#: failure_class, why)
EVENT_SERVE_EVICTION = "serve.eviction"
#: the load policy asked for capacity (fields: kind=grow|shrink,
#: queue_depth, source)
EVENT_SERVE_ELASTICITY = "serve.elasticity"
#: a fabric-probe sweep resolved its link matrix (fields: source=cache|probe,
#: topology, chip, edges, seconds, slowest_gbps — telemetry/fabric.py)
EVENT_FABRIC_PROBE = "fabric.probe"

ALL_EVENTS = frozenset({
    EVENT_COMPILE,
    EVENT_RETRY,
    EVENT_RETRY_EXHAUSTED,
    EVENT_RETRY_REFUSED,
    EVENT_DESCENT,
    EVENT_FAULT,
    EVENT_DIVERGENCE,
    EVENT_TUNE_DECISION,
    EVENT_TUNE_TRIAL,
    EVENT_EXCHANGE_ROUTE,
    EVENT_STEP_OVERLAP,
    EVENT_STEP_HALO,
    EVENT_KERNEL_STORAGE_DTYPE,
    EVENT_CHECKPOINT_SAVE,
    EVENT_CHECKPOINT_RESTORE,
    EVENT_CHECKPOINT_FALLBACK,
    EVENT_SUPERVISOR_RESTART,
    EVENT_WATCHDOG_STALL,
    EVENT_PROFILE_CAPTURE,
    EVENT_RESHARD,
    EVENT_RESHARD_FALLBACK,
    EVENT_SUPERVISOR_REPLENISH,
    EVENT_SERVE_ADMISSION,
    EVENT_SERVE_SHED,
    EVENT_SERVE_EVICTION,
    EVENT_SERVE_ELASTICITY,
    EVENT_FABRIC_PROBE,
    NUMERICS_DRIFT,
})

# the three events that double as host spans (see "spans" above)
ALL_SPANS = ALL_SPANS | {EVENT_COMPILE, EVENT_RETRY, EVENT_CHECKPOINT_SAVE}

# --- Pallas kernel names (``pl.pallas_call(name=...)``) ------------------------
#
# One name per kernel FAMILY, stable across depth, radius, shape and dtype
# (those are in the op's shape already).  The name is the kernel's
# ``kernel_name`` in the TPU custom call and the last scope of its HLO
# ``op_name`` (``.../exchange.z/blend_slab/pallas_call``), which is how the
# benchmark's named per-layer metrics find it.  Identifiers (``[a-z0-9_]+``):
# ``mlir.sanitize_name`` leaves them alone.

KERNEL_JACOBI_WRAP = "jacobi_wrap_step"
KERNEL_JACOBI_SHELL_WAVEFRONT = "jacobi_shell_wavefront_step"
KERNEL_JACOBI_ZRING_WAVEFRONT = "jacobi_zring_wavefront_step"
KERNEL_JACOBI_SLAB = "jacobi_slab_step"
KERNEL_JACOBI_PLANE = "jacobi_plane_step"
KERNEL_STREAM_PLANE_PASS = "stream_plane_pass"
KERNEL_STREAM_WAVEFRONT_PASS = "stream_wavefront_pass"
KERNEL_STREAM_WRAP_PASS = "stream_wrap_pass"
KERNEL_MEAN6_SHELL_WAVEFRONT = "mean6_shell_wavefront_step"
KERNEL_MEAN6_PLANE = "mean6_plane_step"
#: the halo writes: whole x planes, a static y/z sliver, a traced-offset one
KERNEL_BLEND_PLANES = "blend_planes"
KERNEL_BLEND_SLAB = "blend_slab"
KERNEL_BLEND_SLAB_DYNAMIC = "blend_slab_dynamic"
KERNEL_PACK_SLAB = "pack_slab"
KERNEL_UNPACK_SLAB = "unpack_slab"
KERNEL_PACK_ZSHELL = "pack_zshell"
KERNEL_UNPACK_ZSHELL = "unpack_zshell"
KERNEL_PACK_YSHELL = "pack_yshell"
KERNEL_UNPACK_YSHELL = "unpack_yshell"

ALL_KERNELS = frozenset({
    KERNEL_JACOBI_WRAP,
    KERNEL_JACOBI_SHELL_WAVEFRONT,
    KERNEL_JACOBI_ZRING_WAVEFRONT,
    KERNEL_JACOBI_SLAB,
    KERNEL_JACOBI_PLANE,
    KERNEL_STREAM_PLANE_PASS,
    KERNEL_STREAM_WAVEFRONT_PASS,
    KERNEL_STREAM_WRAP_PASS,
    KERNEL_MEAN6_SHELL_WAVEFRONT,
    KERNEL_MEAN6_PLANE,
    KERNEL_BLEND_PLANES,
    KERNEL_BLEND_SLAB,
    KERNEL_BLEND_SLAB_DYNAMIC,
    KERNEL_PACK_SLAB,
    KERNEL_UNPACK_SLAB,
    KERNEL_PACK_ZSHELL,
    KERNEL_UNPACK_ZSHELL,
    KERNEL_PACK_YSHELL,
    KERNEL_UNPACK_YSHELL,
})

#: every registered name, any kind — what the lint checks literals against
ALL_NAMES = (
    ALL_COUNTERS | ALL_GAUGES | ALL_HISTOGRAMS | ALL_SPANS | ALL_EVENTS | ALL_KERNELS
)
