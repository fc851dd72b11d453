"""Counters: the process-wide metrics pillar of the telemetry subsystem.

A flat registry of named counters (monotone adds) and gauges (last-set
values), always on — incrementing a counter is a dict add under a lock,
cheap enough to leave in every code path unconditionally, so the
instrumented call sites (solvers, checkpoints, watchdog, multihost init)
never need to know whether telemetry is configured. Snapshots are
written as JSON at exit by :func:`poisson_tpu.obs.configure` (to
``--metrics-out`` and/or ``metrics-rank{R}.json`` in the trace dir) and
per-rank snapshots merge with :func:`merge` (counters sum across ranks;
gauges keep per-rank values — a max would hide a straggler).

Naming convention (dotted, low cardinality):

- ``pcg.solves.<verdict>`` / ``pcg.iterations.<verdict>`` — solve count
  and iteration count by stop-flag name (``solvers.pcg.FLAG_NAMES``);
- ``resilient.restarts`` / ``resilient.escalations``;
- ``checkpoint.writes`` / ``checkpoint.crc_failures`` /
  ``checkpoint.corrupt`` / ``checkpoint.generation_fallbacks``;
- ``watchdog.beats`` / ``watchdog.stalls``;
- the ``integrity`` family — the numerical-integrity layer
  (``poisson_tpu.integrity``, the silent-data-corruption defense):
  ``integrity.checks`` counts chunk-boundary drift verifications run by
  the resilient driver (one extra stencil application each; the in-loop
  probe's per-iteration checks are fused device work and deliberately
  uncounted), ``integrity.detections`` counts confirmed FLAG_INTEGRITY
  verdicts, ``integrity.verified_restarts`` counts recoveries that
  restarted from the last *verified-good* snapshot (never a precision
  escalation — a bit flip is a hardware event, not an arithmetic one),
  and ``integrity.false_alarms`` counts detections the driver's
  host-side recheck could not reproduce (the solve resumes from the
  very state that fired; a misfiring detector costs one recheck, never
  a restart). Read ``false_alarms`` next to ``detections``: a nonzero
  ratio on clean fleets means the drift tolerance is mis-sized;
- the ``serve.integrity`` family — the solve service's SDC response
  (``ServicePolicy.integrity``): ``serve.integrity.detections``
  (FLAG_INTEGRITY members classified), ``serve.integrity.retries``
  (typed ``integrity`` retries issued),
  ``serve.integrity.suspect_cohorts`` (distinct (backend, device_kind)
  hardware cohorts tainted SDC-suspect by a first detection — cohorts,
  not detections), and ``serve.integrity.suspect_dispatches``
  (dispatches that ran DEFENSIVE verification only because their
  cohort was suspect — the cost of paying the probe after the first
  strike instead of always); terminal failures land in
  ``serve.errors.integrity`` beside the other typed error classes;
- ``multihost.init_retries`` / ``multihost.degraded``;
- ``time.compile_seconds`` / ``time.execute_seconds`` (accumulating
  float counters: compile vs execute wall time);
- ``compile_cache.hits`` / ``compile_cache.misses`` — JAX persistent
  compilation cache traffic (``utils.compile_cache``: the cache in
  ``$JAX_COMPILATION_CACHE_DIR`` or ``<repo>/.jax_cache``), read next to
  ``time.compile_seconds`` to answer "reused or recompiled?";
- ``batched.solves`` / ``batched.padding_members`` /
  ``batched.bucket_cache.hits`` / ``batched.bucket_cache.misses`` —
  multi-RHS driver traffic (``solvers.batched``): members solved, padding
  overhead, and whether ragged batch sizes are reusing bucket
  executables;
- ``batched.fused.dispatches`` / ``batched.fused.members`` — batched
  dispatches that ran on the member-axis Pallas kernels
  (``ops.pallas_cg._fused_solve_batched``: TPU, fp32, scaled, no mesh/
  geometries/MG/block/verify) and the members they carried: how often
  the fused path engages beside the XLA families;
- ``batched.mg.dispatches`` / ``batched.mg.members`` — batched
  dispatches that ran the vmapped MG-preconditioned loop
  (``mg.preconditioner._solve_batched_mg``, ``preconditioner="mg"``) and
  the members they carried (padding excluded, as ``mg.solves`` counts
  them); their level hierarchy is looked up under the span
  ``solve_batched.prepare``;
- ``geom.cache.hits`` / ``geom.cache.misses`` — the geometry canvas
  cache (``poisson_tpu.geometry.canvas.geometry_setup``), keyed by
  (fingerprint, grid box, f_val, dtype, scaled) the way the jit cache
  keys shapes: a **miss** pays one host-side fp64 canvas bake
  (closed-form segment lengths or adaptive SDF face sampling) + cast +
  transfer; a **hit** reuses the device arrays across requests,
  buckets, and lane splices. Read next to
  ``batched.bucket_cache.{hits,misses}`` to tell the two reuse stories
  apart: a NEW geometry family on a warm grid is a ``geom.cache.miss``
  + ``batched.bucket_cache.hit`` pair (new canvases, zero recompiles —
  the mixed-geometry co-batching claim, measured);
- ``serve.requeued.geometry_isolated`` — requeues that applied
  geometry-FINGERPRINT taint on top of the request-id mutual taint
  (``serve.service``): a batch kill in a mixed-geometry bucket marks
  the co-failed *families*, so a bad geometry can never re-co-batch
  with its batchmates under a fresh request id;
- ``profile.captures`` / ``profile.errors`` — programmatic profiler
  captures (``obs.profile``);
- the ``serve`` family — the solve service's request ledger
  (``poisson_tpu.serve``), the counters the chaos campaign's
  no-lost-request invariant is asserted from
  (``admitted == completed + errors + shed`` once drained):
  ``serve.admitted`` / ``serve.completed`` (with
  ``serve.completed.partial`` and ``serve.completed.recovered``
  sub-counts) / ``serve.errors`` by typed class
  (``serve.errors.{divergence,transient,internal,integrity,placement}``)
  / ``serve.shed`` by typed reason
  (``serve.shed.{queue_full,breaker_open,deadline_expired}``);
  lifecycle machinery: ``serve.dispatches`` / ``serve.batch_members`` /
  ``serve.retries`` / ``serve.backoff_seconds`` /
  ``serve.requeued.isolated`` / ``serve.escalations`` /
  ``serve.deadline.{expired_in_queue,expired_mid_solve}`` /
  ``serve.breaker.{trips,half_opens,closes}`` / the degradation ladder
  ``serve.degraded.{padding,iteration_cap,precision}``; plus the
  deadline stops the chunked drivers count
  (``checkpoint.deadline_stops`` / ``resilient.deadline_stops``);
- the ``serve.refill`` family — the continuous-batching lane table
  (``serve.refill`` + ``solvers.lanes``, ``ServicePolicy.scheduling=
  "continuous"``): ``serve.refill.splices`` (queued RHS spliced into
  freed lanes of a running bucket executable) /
  ``serve.refill.retired_lanes`` (lanes retired to a typed outcome at a
  chunk boundary) / ``serve.refill.idle_lane_steps`` (Σ EMPTY lanes per
  chunk step — the fused width paid for open seats) /
  ``serve.refill.refill_denied_by_breaker`` (refill decisions refused
  by an open cohort breaker);
- the ``serve.fleet`` family — the durable solve fleet (``serve.fleet``,
  ``ServicePolicy.fleet``): ``serve.fleet.quarantines`` (workers pulled
  from scheduling after a crash/hang/stall verdict) /
  ``serve.fleet.restarts`` (quarantined workers returned through
  warm-up; ``serve.fleet.warmup_solves`` and
  ``serve.fleet.warmup_failures`` count the sticky-bucket recompiles) / ``serve.fleet.worker_deaths`` (restart
  budget exhausted — the worker never schedules again) /
  ``serve.fleet.hangs`` (stall verdicts from the worker heartbeat
  watchdog, landing next to ``watchdog.stalls``) /
  ``serve.fleet.recovered_requests`` (in-flight requests pulled off a
  fallen worker and re-dispatched to survivors with mutual taint) /
  ``serve.fleet.sticky_{hits,misses}`` (routing that found/missed a
  worker already holding the queue head's bucket executable) /
  ``serve.fleet.device_losses`` (DEVICE fault domains marked lost —
  counted per device, not per worker or per dispatch: a
  ``DeviceLossError`` quarantines every worker bound to the device,
  bumps the placement epoch, and all of that is ONE loss; read next to
  ``serve.fleet.quarantines`` to tell "a worker fell" from "the
  silicon under N workers vanished");
- the ``serve.placement`` family — the device placement registry
  (``serve.placement``, ``FleetPolicy.devices``):
  ``serve.placement.binds`` (worker→device bindings handed out) /
  ``serve.placement.rebinds`` (quarantined workers rebound to a
  SURVIVING device at restart — the topology-aware half of a fleet
  restart; their sticky executables recompile on the new device
  through the ordinary warm-up) / ``serve.placement.remapped``
  (journal-recovered requests whose recorded device no longer exists
  on this topology, remapped AUDIBLY to a survivor — each also
  carries a ``placement_remapped`` flight point; silence here while
  ``serve.recovered`` moves after a topology change means work is
  resuming onto ghost device ids, the exact failure this counter
  exists to rule out) / ``serve.placement.replans`` (elastic
  re-plans of sharded dispatches onto the surviving topology; the
  ladder rungs land on ``serve.degraded.mesh_shrink`` /
  ``serve.degraded.single_device`` / ``serve.degraded.mesh_shed``,
  counted like the queue-depth ladder) / gauges
  ``serve.placement.devices`` / ``serve.placement.alive`` /
  ``serve.placement.epoch`` (the placement epoch — bumped on every
  loss, carried by journal records so recovery can see the topology
  changed);
- the ``serve.journal`` family — the crash-safe write-ahead journal
  (``serve.journal``): ``serve.journal.records`` (CRC-sealed lifecycle
  transitions appended) / ``serve.journal.write_errors`` (appends the
  disk refused — durability degraded, audibly) /
  ``serve.journal.replays`` (recovery replays run) /
  ``serve.journal.torn_records`` (torn-tail or CRC-failing records
  skipped audibly during replay — never trusted, never fatal);
  ``serve.recovered`` counts requests re-enqueued from a replay — NOT
  re-counted as ``serve.admitted`` (the crashed process already counted
  the admission), which is what closes the ledger invariant across a
  kill/replay boundary when per-process snapshots merge;
- ``serve.dedup.hits`` — idempotent submissions deduplicated against
  the ledger (``ServicePolicy.dedup``): a client retry or replayed
  submit whose ``request_id`` was already seen returns the original
  outcome instead of double-admitting;
- the ``mg`` family — the geometric multigrid preconditioner
  (:mod:`poisson_tpu.mg`, ``preconditioner="mg"``): ``mg.solves``
  counts MG-preconditioned solves dispatched (batched members count
  individually — read next to ``pcg.solves.*`` to see the rollout
  fraction); ``mg.hierarchy_cache.hits`` / ``mg.hierarchy_cache.misses``
  — the fingerprint-keyed device hierarchy cache
  (``mg.hierarchy.device_hierarchy``): a **miss** pays the host-fp64
  level build (coefficient coarsening per level + the dense coarsest
  factorisation, the expensive part) + cast + transfer; a **hit**
  reuses the device levels across solves, buckets, and lane tables of
  the same (problem, dtype, geometry-fingerprint, config). Read next
  to ``geom.cache.{hits,misses}`` — the same setup-reuse story, one
  level up;
- the ``krylov`` family — Krylov memory (:mod:`poisson_tpu.krylov`:
  block-CG batched mode and fingerprint-keyed subspace recycling):
  ``krylov.cache.hits`` / ``krylov.cache.misses`` — deflation-basis
  cache lookups (``krylov.recycle``), keyed by (geometry fingerprint,
  grid box, dtype, scaled, preconditioner): a **miss** runs the
  harvest-enabled cold solve; a **hit** runs the warm deflated solve
  against the cached basis. Read next to ``geom.cache.{hits,misses}``
  — the same fingerprint-reuse story, one tier deeper (canvases make
  a repeat operator's *setup* cheap; the basis makes its *iterations*
  cheap); ``krylov.cache.evictions`` — entries LRU-dropped over the
  byte budget (``KrylovPolicy.budget_bytes``);
  ``krylov.cache.invalidations`` — entries dropped AUDIBLY for cause
  (SDC-suspect harvest cohort, divergence/integrity escalation,
  journal recovery, a failed warm solve — each emits a
  ``krylov.invalidate``/``krylov.fallback`` event with the reason);
  ``krylov.harvests`` — converged cold solves whose Lanczos window
  yielded a cached basis; ``krylov.warm_solves`` — warm deflated
  solves that converged; ``krylov.iterations_saved`` — net iterations
  saved by warm solves (Σ of the family's cold count minus the warm
  count; an unlucky warm solve subtracts honestly);
  ``krylov.fallbacks`` — warm solves that did NOT converge and fell
  back to a cold solve (stale/poisoned basis: costs a retry, never a
  wrong answer — nonzero here with a healthy fleet means bases are
  going stale faster than they are used);
  ``krylov.block.solves`` — members dispatched through the block
  recurrence (``solve_batched(mode="block")``; read next to
  ``batched.solves`` for the rollout fraction);
  ``krylov.block.rank_deficient`` — block dispatches whose B×B solves
  truncated a rank-deficient direction (graceful degradation on
  near-parallel RHS columns, not a failure; a high ratio to
  ``krylov.block.solves`` means the traffic's batches are too
  clustered to benefit from block width);
- ``serve.krylov.verify_suspensions`` — dispatches where demanded
  integrity verification (always-on policy stride, or a suspect
  hardware cohort arming the defensive stride) met a Krylov program
  that has no verified form yet: the SDC defense WINS — the request
  falls back to the verified independent/chunked path, the block/
  deflation acceleration is suspended for that dispatch, and this
  counter (plus a ``krylov.verify_suspended`` event) is the audible
  record. Nonzero on a suspect fleet means the ``:blk``/``:defl``
  cohorts are paying cold verified solves — route the traffic back to
  independent mode or clear the suspicion;
- ``serve.krylov.sticky_hits`` / ``serve.krylov.sticky_misses`` —
  basis-holder routing (the second stickiness axis beside
  ``serve.fleet.sticky_*``): a deflation-class queue head routed to
  the worker already holding its fingerprint's basis (hit) or falling
  back to ordinary routing because the holder is quarantined/dead
  (miss; only counted for deflation heads with a recorded holder, so
  the ratio reads as basis-affinity effectiveness);
- the ``serve.slo`` family — the flight recorder's SLO accounting
  (``obs.flight.SLOTracker``, objectives declared in
  ``serve.types.SLOPolicy``): ``serve.slo.good`` / ``serve.slo.bad``
  count outcomes for/against the objective (good = a converged result
  delivered within ``latency_objective_seconds``; sheds, typed errors,
  partials, and slow successes are bad — they spend error budget);
  ``serve.degraded.slo_driven`` counts load-level decisions where the
  burn rate (not queue depth) chose the degradation rung
  (``SLOPolicy.degrade_on_burn``);
- the ``serve.tenant`` family — tenant isolation & overload fairness
  (:mod:`poisson_tpu.serve.tenancy`, ``ServicePolicy.tenancy``; the
  whole family is silent with tenancy off):
  ``serve.tenant.quota_sheds`` — admissions refused by a tenant's
  token-bucket quota (each is also a typed ``serve.shed.quota_exceeded``
  outcome with zero compute burned);
  ``serve.tenant.promotions`` — deficit-weighted-round-robin head
  rotations (a pump where the fair-share pick was not already at the
  queue front; within-tenant FIFO order is preserved);
  ``serve.tenant.lane_deferred`` — refill splices deferred because the
  candidate's tenant already held its fair share of the bucket's lanes
  while a competitor had eligible work waiting (deferred to the next
  refill, never shed);
  ``serve.tenant.retry_exhausted`` — retries converted into typed
  errors because the tenant's retry budget was empty (each also emits
  a ``serve.tenant.retry_exhausted`` event; the budget bounds a
  poisoned tenant's dispatches at admitted + retry_budget);
  ``serve.tenant.degraded_offender`` / ``serve.tenant.degraded_spared``
  — tenant-scoped degradation decisions: dispatches/splices that paid
  the full queue-pressure rung as the offending tenant (largest
  backlog/share ratio) vs ran one rung gentler as a non-offender;
  per-tenant counters ``serve.tenant.{admitted,completed,errors,shed,
  retries,dispatches}.<tenant>`` — the tenant-split ledger (the global
  ``serve.*`` equation restricted to one client; the chaos campaign
  closes it per tenant);
  the ``serve.tenant.slo.<tenant>.*`` family — one
  ``obs.flight.SLOTracker`` per tenant publishing good/bad counters,
  the latency histogram, budget and burn-rate gauges under the
  tenant's own prefix (the global ``serve.slo.*`` surface is scored
  exactly once, by the fleet tracker);
  gauges ``serve.tenant.share.<tenant>`` (configured relative weight),
  ``serve.tenant.quota_tokens.<tenant>`` (admission bucket level),
  ``serve.tenant.retry_tokens.<tenant>`` (remaining retry budget; -1 =
  budgeting off), and ``serve.tenant.slo_burn.<tenant>`` (the
  shortest-window burn rate — the scoreboard's per-tenant SLO column);
- the ``session`` family — durable solver sessions (ordered streams of
  dependent solves: :mod:`poisson_tpu.serve.session` hosts them,
  :mod:`poisson_tpu.solvers.session` runs the steps):
  ``session.opens`` / ``session.closes`` — session lifecycles started
  and retired through :class:`~poisson_tpu.serve.session.SessionHost`;
  ``session.steps`` — individual step solves executed (cold or warm;
  read next to ``session.warm.hits`` for the warm fraction);
  ``session.warm.hits`` — steps that ran the warm-started program
  because the offered iterate passed the validity gate (fingerprint
  drift within ``SessionPolicy.warm_drift_bound`` + residual sanity
  within ``warm_residual_factor``); ``session.warm.fallbacks`` — steps
  where a warm start was OFFERED and rejected by the gate, so the step
  ran cold AUDIBLY (each emits a ``session.warm.fallback`` event with
  the reason — ``family``, ``drift``, or ``residual``; a cold step
  with nothing offered counts neither); ``session.setup.hits`` /
  ``session.setup.misses`` — the shifted-operator (implicit-Euler
  heat) setup cache, the same canvas-reuse story as
  ``geom.cache.{hits,misses}`` one mass-shift deeper;
  ``session.design.steps`` — shape-optimization design iterations
  (one ``shape_gradient`` adjoint solve + parameter update each);
  ``session.step.deadline_misses`` — steps whose wall time exceeded
  ``SessionPolicy.step_deadline_seconds`` (the result is still
  delivered; the miss is recorded on the session's flight trace);
  ``session.slo.good`` / ``session.slo.bad`` — per-*session* SLO
  verdicts at close (good = zero step errors and total wall within
  ``SessionPolicy.slo_seconds``; the per-step ``serve.slo.*`` family
  still scores each step individually); ``session.recovered`` —
  sessions re-opened from the journal by ``--recover`` at the exact
  committed step boundary (mid-step work re-enqueues cold, warm state
  is never resurrected from unreplayed device memory);
  ``session.recovery_errors`` — journaled sessions whose recovery
  failed to reconstruct (malformed params/geometry — skipped audibly,
  never half-restored); ``session.callback_errors`` — ``on_solution``
  hooks that raised (the step's outcome is unaffected);
  ``serve.session.shed_opens`` — session opens refused by admission
  control (session-count cap or queue pressure past
  ``SessionPolicy.shed_open_at``): the degradation ladder's session
  rung sheds NEW sessions before it sheds steps of in-flight ones,
  and each refusal is a typed ``serve.shed`` outcome plus a
  ``session.shed_open`` event, never a silent drop.

- the ``contracts`` family — the static program-contract checker
  (:mod:`poisson_tpu.contracts`, ``python -m poisson_tpu.contracts``):
  gauges ``contracts.findings`` (unsuppressed lint + drift findings on
  the tree — nonzero means a contract is drifting *now*, before any
  byte-pin fires), ``contracts.suppressed`` (inline-suppressed
  findings, each carrying a reason string), and ``contracts.rules``
  (active rule count). ``bench.py`` stamps all three on every run so
  drift is visible in the same Prometheus exposition as the perf
  telemetry it protects.

Gauge families (``obs.costs`` sets these; ``obs.export`` exposes both
counters and numeric gauges in Prometheus text format):

- ``cost.hlo_{flops,bytes}_per_iter`` / ``cost.model_{flops,bytes}_per_iter``
  / ``cost.model_agreement`` / ``cost.peak_memory_bytes`` — one compiled
  PCG iteration body vs the analytic 5-point-stencil model;
- ``cost.solve.{flops,bytes_accessed,peak_memory_bytes}`` — the whole
  jitted solve program;
- ``cost.mg.{bytes_per_cycle,flops_per_cycle,passes}`` — the analytic
  V-cycle traffic model (``obs.costs.mg_vcycle_cost``): what one MG
  preconditioner application moves per CG iteration, the number that
  cohorts MG records separately in roofline attribution;
- ``mg.levels`` (hierarchy depth of the most recent build) and
  ``mg.coarse_dense`` (1 when the coarsest level solves by the dense
  inverse, 0 when it fell back to smoother sweeps — an audible
  quality bit: the dense coarse solve is what makes the cycle
  resolution-independent); ``mg.pallas_levels`` (set by ``pcg_solve``:
  how many levels of the solve's V-cycle smoothed on the Pallas strip
  kernels, ``ops.pallas_mg``, on one device or on a mesh's blocks; 0
  off a TPU);
  ``mg.replicated_from`` (set by ``pcg_solve`` over a mesh: the level
  at which the sharded V-cycle gathers its right-hand side and runs the
  rest of the cycle whole on every device, ``parallel.mg_sharded``);
- ``cost.krylov.{block_bytes_per_iter,block_flops_per_iter,
  block_passes_per_member}`` and ``cost.krylov.{deflated_bytes_per_iter,
  deflated_flops_per_iter,deflated_passes}`` — the analytic block/
  deflated iteration traffic models (``obs.costs.krylov_block_cost`` /
  ``krylov_deflated_cost``): what a ``:blk``/``:defl`` cohort's
  iteration moves, so roofline attribution prices the
  fewer-iterations-for-more-bytes-per-iteration trade instead of
  averaging it away;
- ``serve.krylov.{cold_p50_seconds,warm_p50_seconds,cold_p99_seconds,
  warm_p99_seconds}`` — the repeat-fingerprint open-loop bench's
  cold-vs-warm latency split (``bench.py --serve --repeat-fingerprint``;
  cold = the family's first request, warm = repeats against the cached
  basis), stamped per run so the forensics report can render the
  warm-start win beside the ``krylov.*`` counters;
- ``roofline.{achieved_gbps,peak_gbps,fraction}`` — measured throughput
  against the platform bandwidth ceiling;
- ``export.http_port`` — the live ``/metrics`` endpoint's bound port;
- ``compile_cache.dir`` — the persistent-compilation-cache directory in
  use (``utils.compile_cache``; a string gauge, skipped audibly by the
  Prometheus exposition);
- ``batched.last_bucket`` — the bucket width the most recent batched
  dispatch padded to (read next to ``batched.padding_members`` to see
  how much of the fused width was padding);
- bench headline gauges, one per ``bench.py`` mode so the latest run's
  verdict is scrapeable beside its counters: ``bench.mlups`` /
  ``bench.vs_baseline`` (single-solve mode), ``bench.batched_solves_per_sec``
  / ``bench.batched_speedup`` (``--batch``; the CLI's
  ``solve-batched --json`` stamps the same measurement as
  ``batched.solves_per_sec``), ``bench.verify_overhead_fraction``
  (``--verify-every`` A/B overhead), and ``bench.session_steps_per_sec``
  / ``bench.session_speedup`` (``--session`` — the durable-session
  stream's throughput and its warm-vs-cold win over the same moving-
  ellipse schedule);
- ``serve.queue_depth`` / ``serve.load_level`` / ``serve.shed_rate`` /
  ``serve.lost_requests`` / ``serve.p99_latency_seconds`` — service
  health, refreshed on every drain; ``serve.latency_seconds`` is a
  ``{"p50": …, "p95": …, "p99": …}`` dict that ``obs.export`` renders as
  a Prometheus summary with quantile labels;
- ``serve.refill.active_lanes`` (occupancy after the latest chunk step)
  and ``serve.sustained_solves_per_sec`` / ``serve.drain_solves_per_sec``
  (the open-loop A/B headline, ``bench.py --serve --arrival-rate``);
- ``serve.fleet.workers`` (configured pool size) and
  ``serve.fleet.live_workers`` (workers currently RUNNING — refreshed
  on every quarantine/restart/death, so a shrinking fleet is visible
  at scrape time);
- the SLO surface (``obs.flight.SLOTracker``; all on the service
  clock): ``serve.slo.latency_seconds`` is a REAL latency histogram —
  a ``{"le": {bucket: cumulative_count}, "sum": …, "count": …}`` dict
  that ``obs.export`` renders as a Prometheus *histogram*
  (``…_bucket{le="…"}``/``…_sum``/``…_count``), so burn-rate alerting
  re-thresholds the distribution at scrape time instead of trusting
  pre-baked percentiles; ``serve.slo.budget_remaining`` is the fraction
  of the cumulative error budget left (1.0 = untouched, negative = an
  honest overdraft); ``serve.slo.burn_rate.{W}s`` is the trailing
  W-second burn rate, one gauge per ``SLOPolicy.burn_windows`` entry
  (burn 1.0 = spending budget exactly at the availability target;
  multi-window alerting ANDs a short and a long window);
  ``serve.slo.objective_seconds`` echoes the declared latency
  objective so the exposition is self-describing.

- the ``obs.forecast`` family — the convergence observatory
  (:mod:`poisson_tpu.obs.forecast`): counters
  ``obs.forecast.predictions`` (completed solves graded against the
  prediction that was live at their admission — one predict-then-
  compare each), ``obs.forecast.cold_cohorts`` (gradings where the
  prediction came from the analytic √(M·N)/bandwidth seed because the
  cohort had no samples yet — a high rate means traffic never
  repeats, so ETAs are model-quality, not measured),
  ``obs.forecast.snapshot.saves`` / ``obs.forecast.snapshot.loads``
  (CRC-sealed forecast snapshots written beside the journal / warm-
  loaded on recovery), ``obs.forecast.snapshot.torn`` (snapshots
  rejected at load for CRC/shape/version mismatch — the model starts
  cold AUDIBLY, a corrupt forecast never poisons admission), and
  ``obs.forecast.snapshot.write_errors`` (save attempts that failed
  on disk — durability degraded, audibly). Gauges:
  ``obs.forecast.abs_err_pct`` (the most recent grading's absolute
  iteration-count error, percent of actual),
  ``obs.forecast.calibration_err_pct`` (the running p50 absolute
  error — THE calibration figure; ``bench.py --serve`` stamps it on
  every record and ``regress.py`` lifts it into the sentinel cohort
  with a lower-is-better pin), and ``obs.forecast.calibration_pct`` —
  a real histogram of per-solve absolute percent errors (the same
  ``{"le": …, "sum": …, "count": …}`` shape as
  ``serve.slo.latency_seconds``, rendered as a Prometheus histogram)
  so calibration drift is re-thresholdable at scrape time.

- the ``serve.forecast`` family — predicted-deadline admission
  (``ServicePolicy.forecast``): ``serve.forecast.admission_checks``
  (requests whose deadline was compared against the cohort's p90 ETA
  at submit), ``serve.shed.predicted_deadline`` (the typed shed: the
  p90 ETA exceeded the deadline × margin, so the request was refused
  BEFORE any dispatch — zero compute burned, the counter the chaos
  drill asserts), ``serve.forecast.preempted`` (admitted deadline
  work retired early at a lane/chunk boundary because the re-forecast
  — measured log-residual slope over the remaining budget — said the
  deadline cannot be met; each also sheds typed
  ``predicted_deadline``), ``serve.forecast.backlog_seconds`` (gauge:
  the queue's summed p50 ETAs — backlog measured in work-seconds,
  not request count), and ``serve.degraded.backlog_driven`` (ladder
  rungs chosen because ETA backlog, not raw depth, crossed the
  fraction — the forecast-aware sibling of
  ``serve.degraded.slo_driven``).

- the ``obs.roofline`` family — the roofline observatory
  (:mod:`poisson_tpu.obs.roofline`): counters
  ``obs.roofline.observations`` (measured dispatches and lane
  chunk-steps graded — achieved GB/s from the backend's effective-pass
  model over the measured wall, as a fraction of the platform
  bandwidth ceiling), ``obs.roofline.cold_cohorts`` (gradings against
  the analytic prior because the cohort had no measured samples yet),
  ``obs.roofline.skipped`` (unmeasurable dispatches — zero measured
  wall or zero iterations; a VirtualClock drill that never advances
  time produces only these, deliberately),
  ``obs.roofline.snapshot.{saves,loads,torn,write_errors}`` (the
  CRC-sealed journal-adjacent profile snapshot, same save/load/torn
  contract as ``obs.forecast.snapshot.*``). Gauges:
  ``obs.roofline.fraction`` (the most recent measured fraction of
  peak), ``obs.roofline.fraction.*`` (running p50 measured fraction
  per backend — the scalar the ``top`` Backends pane and the router's
  warm evidence read), ``obs.roofline.abs_err_pct`` (the most recent
  grading's |expected − measured| fraction error, percent of
  expected), ``obs.roofline.calibration_err_pct`` (the running p50 of
  those errors — the calibration figure ``bench.py --serve`` stamps),
  and ``obs.roofline.calibration_pct`` (a real histogram of per-
  observation percent errors, rendered as a Prometheus histogram).

- the ``serve.router`` family — the cost-model backend router
  (:mod:`poisson_tpu.serve.router`, ``ServicePolicy.router``):
  ``serve.router.decisions`` (dispatches routed) split into
  ``serve.router.{cold_decisions,warm_decisions}`` (cold = the
  analytic policy table — VMEM-resident small grids, CA on the HBM
  plateau, xla elsewhere; warm = ranked by measured per-cohort
  roofline evidence) with per-arm ``serve.router.chosen.*``;
  ``serve.router.mispredictions`` (measured dispatches landing below
  ``misprediction_fraction`` × the cohort's expected fraction — each
  also emits a typed ``serve.router.misprediction`` event);
  ``serve.router.demotions`` (arms benched after ``demote_after``
  consecutive mispredictions, breaker-style),
  ``serve.router.half_opens`` (benched arms re-probed after cooldown)
  and ``serve.router.recoveries`` (probes that measured healthy and
  closed the arm); ``serve.router.executor_fallbacks`` (routed
  non-xla choices executed on the proven xla path — the execution
  gate that holds until the Pallas kernels have a valid hardware
  measurement, see ``serve.router.executor_backend``);
  ``serve.degraded.backend_downshift`` (the degradation ladder's
  backend rung: queue pressure past ``downshift_at`` forces the xla
  floor arm). Gauge ``serve.router.demoted_arms`` — currently benched
  (backend, device) arms.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Optional

_LOCK = threading.Lock()
_COUNTERS: dict[str, float] = {}
_GAUGES: dict[str, object] = {}


def inc(name: str, value: float = 1) -> None:
    """Add ``value`` to counter ``name`` (creating it at 0)."""
    with _LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + value


def gauge(name: str, value) -> None:
    """Set gauge ``name`` to ``value`` (last write wins)."""
    with _LOCK:
        _GAUGES[name] = value


def get(name: str, default: float = 0) -> float:
    """Current value of counter ``name`` (0 when never incremented)."""
    with _LOCK:
        return _COUNTERS.get(name, default)


def reset() -> None:
    """Clear the registry (tests; a library user embedding several runs
    in one process)."""
    with _LOCK:
        _COUNTERS.clear()
        _GAUGES.clear()


def snapshot(rank: Optional[int] = None) -> dict:
    """The registry as one JSON-ready dict, stamped with rank and both
    clocks (wall for cross-host alignment, monotonic for stall math)."""
    if rank is None:
        from poisson_tpu.obs.trace import default_rank

        rank = default_rank()
    with _LOCK:
        return {
            "schema": "poisson_tpu.obs.metrics/1",
            "rank": rank,
            "pid": os.getpid(),
            "at_unix": time.time(),
            "at_mono": time.monotonic(),
            "counters": dict(_COUNTERS),
            "gauges": dict(_GAUGES),
        }


def write_snapshot(path: str, rank: Optional[int] = None) -> None:
    """Atomically write :func:`snapshot` to ``path``. Best-effort: a
    failing metrics disk must never take the solve down with it."""
    snap = snapshot(rank)
    tmp = f"{path}.{os.getpid()}.tmp"
    try:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(tmp, "w") as f:
            json.dump(snap, f, sort_keys=True, indent=1, default=str)
        os.replace(tmp, path)
    except OSError:
        try:
            if os.path.exists(tmp):
                os.remove(tmp)
        except OSError:
            pass


def merge(snapshots: list[dict]) -> dict:
    """Merge per-rank snapshots: counters sum; gauges are kept per rank
    under ``gauges_by_rank`` (aggregating them would hide stragglers)."""
    counters: dict[str, float] = {}
    gauges_by_rank: dict[str, dict] = {}
    ranks = []
    for snap in snapshots:
        if not isinstance(snap, dict):
            continue
        rank = snap.get("rank", "?")
        ranks.append(rank)
        for name, val in (snap.get("counters") or {}).items():
            try:
                counters[name] = counters.get(name, 0) + val
            except TypeError:
                continue
        g = snap.get("gauges") or {}
        if g:
            gauges_by_rank[str(rank)] = dict(g)
    return {
        "schema": "poisson_tpu.obs.metrics/merged-1",
        "ranks": ranks,
        "counters": counters,
        "gauges_by_rank": gauges_by_rank,
    }


def load_dir(trace_dir: str) -> dict:
    """Read every ``metrics-rank*.json`` under ``trace_dir`` and return
    their :func:`merge` ({} counters when none exist)."""
    snaps = []
    for fname in sorted(os.listdir(trace_dir)):
        if not (fname.startswith("metrics-rank")
                and fname.endswith(".json")):
            continue
        try:
            with open(os.path.join(trace_dir, fname)) as f:
                snaps.append(json.load(f))
        except (OSError, ValueError):
            continue
    return merge(snaps)
