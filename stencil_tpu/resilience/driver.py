"""Checkpoint-rollback recovery driver: the resilient run loop.

``run_resilient(domain, step_fn, n_steps, policy)`` wraps any per-step
engine (``Jacobi3D.step``, ``Astaroth.step``, or a bare closure over a
``DistributedDomain``) with the full detect → degrade → retry ladder
the reference library never had and production stencil codes
(PIConGPU, arXiv:1606.02862) treat as table stakes:

* **checkpoint** every ``ckpt_every`` steps (integrity sha256 in the
  meta record, transient-I/O retry with backoff), after a *blocking*
  health drain so poisoned state is never persisted;
* **watch** via the in-graph :class:`~.health.HealthSentinel` every
  ``check_every`` steps — async readback, the loop never stalls;
* **roll back** to the last good checkpoint when the sentinel trips
  (corrupt checkpoints fall back to older steps automatically), with
  bounded attempts and exponential backoff;
* **degrade** when retries at the current configuration are exhausted:
  drop ``exchange_every`` toward 1, then fall down the capability-aware
  ``pick_method`` priority list (PR 4's fallback, reused) — the caller
  supplies ``rebuild(config)`` to re-realize the engine;
* **preempt cleanly**: SIGTERM (a fleet scheduler reclaiming the host,
  or an injected :class:`~.faults.Preemption`) writes a final
  checkpoint tagged ``preempted`` and returns; the next
  ``run_resilient`` on the same directory resumes from it.

Everything lands in a JSON-serializable :class:`ResilienceReport`
event log — the CI chaos-smoke artifact.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import signal
import threading
import time
from typing import Callable, Dict, List, Optional

from ..analysis.recompile import (ASSERT_SINGLE_COMPILE_ENV,
                                  SingleCompileGuard)
from ..analysis.transfer import hot_loop_transfer_guard
from ..parallel.methods import METHOD_PRIORITY, Method, pick_method
from ..utils.checkpoint import restore_domain, save_domain
from ..utils.logging import LOG_INFO, LOG_WARN
from ..utils.retry import retry
from .faults import FaultPlan
from .health import HealthSentinel, HealthStats


class ResilienceError(RuntimeError):
    """The run could not be kept alive: the sentinel tripped with no
    checkpoint to roll back to, or every retry and degradation was
    exhausted."""


@dataclasses.dataclass
class ResiliencePolicy:
    """Knobs of the resilient loop (see README "Resilience")."""

    check_every: int = 10       # sentinel probe cadence (steps)
    ckpt_every: int = 50        # checkpoint cadence (steps)
    max_retries: int = 3        # rollbacks per configuration
    base_delay: float = 0.05    # backoff seed (seconds), doubles
    save_attempts: int = 3      # transient-I/O retries per save
    max_to_keep: Optional[int] = 3   # checkpoint history depth
    window: int = 8             # sentinel sliding window (probes)
    growth_factor: float = 1e6  # max-abs growth trip factor
    degrade: bool = True        # walk the degradation ladder
    sleep: Callable[[float], None] = time.sleep  # injectable clock
    # megastep execution (parallel/megastep.py): fuse check_every-sized
    # campaign segments into ONE compiled program when the engine
    # provides a segment factory; probe_every sets the in-graph probe
    # cadence INSIDE a segment (1 = per-step trace rows, exact trip
    # location; the segment's final step is always probed)
    fuse_segments: bool = True
    probe_every: int = 1
    # performance observatory (observatory/attribution.py): pair every
    # dispatch's measured seconds/step (block_until_ready-fenced,
    # amortized over the segment's k steps) against the calibrated
    # cost-model prediction of the active plan, exported as
    # stencil_perf_model_error_ratio{entry,method,s}. After
    # drift_window consecutive segments whose ratio departs from its
    # calibrated reference by more than drift_tolerance (relative), a
    # perf_drift event is emitted; retune_on_drift additionally
    # invalidates the plan-cache record (plan_cache_path, default
    # cache) so the tuner re-measures — stale plans heal themselves
    attribute_perf: bool = True
    drift_tolerance: float = 0.5
    drift_window: int = 3
    retune_on_drift: bool = False
    plan_cache_path: Optional[str] = None
    # flight recorder (observatory/recorder.py): bounded black box
    # (recent events + spans + metrics + probe history) dumped
    # atomically into this directory on sentinel trip, degradation,
    # SIGTERM preemption (before the preemption checkpoint), and
    # unhandled dispatch error; None falls back to
    # $STENCIL_FLIGHT_RECORDER_DIR, empty/unset disarms
    flight_recorder_dir: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class StepConfig:
    """One rung of the degradation ladder: the exchange transport and
    temporal-blocking depth the engine should be rebuilt with."""

    method: Method
    exchange_every: int

    def key(self) -> str:
        return f"{self.method.name}[s={self.exchange_every}]"


def degradation_ladder(method: Method, exchange_every: int,
                       runnable: Optional[Callable[[Method], bool]] = None
                       ) -> List[StepConfig]:
    """Successively safer configurations: first halve the temporal-
    blocking depth down to per-step exchanges (deep halos stress the
    fabric hardest), then fall down the ``pick_method`` priority list
    below the current transport.
    ``runnable`` narrows the strategies the ladder may fall to
    (default: all of them)."""
    out: List[StepConfig] = []
    s = int(exchange_every)
    while s > 1:
        s //= 2
        out.append(StepConfig(method, s))
    live = [m for m in METHOD_PRIORITY if runnable is None or runnable(m)]
    if method in live:
        live = live[live.index(method) + 1:]
    out.extend(StepConfig(m, 1) for m in live)
    return out


@dataclasses.dataclass
class ResilienceReport:
    """What happened, machine-readable (the chaos-smoke CI artifact).

    Events flow through the unified telemetry schema
    (:class:`~stencil_tpu.telemetry.EventLog`): every record carries
    the run id, a monotonic sequence number, and the schema version —
    the same shape the campaign service logs, so one scraper reads
    both. The serializable ``events`` list is fed by a ``ListSink``;
    ``sinks`` (e.g. a ``JsonlSink``) fan out the same records live."""

    steps: int = 0
    rollbacks: int = 0
    save_retries: int = 0
    degradations: List[str] = dataclasses.field(default_factory=list)
    preempted: bool = False
    resumed_from: Optional[int] = None
    final_config: str = ""
    run_id: str = ""
    #: did the campaign run fused (megastep) dispatches? False when
    #: fusion was off by policy, the engine provided no segment
    #: factory, or the built path declined — ``fused_decline_reason``
    #: then says WHY (silent stepwise fallbacks used to be invisible)
    fused: bool = False
    fused_decline_reason: str = ""
    #: the machine-readable ``megastep.DECLINE_*`` vocabulary code
    #: behind ``fused_decline_reason`` (a greppable list of causes)
    fused_decline_code: str = ""
    events: List[Dict] = dataclasses.field(default_factory=list)

    def __post_init__(self) -> None:
        from ..telemetry import EventLog, ListSink
        self._elog = EventLog(run_id=self.run_id or None,
                              sinks=(ListSink(self.events),))
        self.run_id = self._elog.run_id
        self._tracer = None

    def add_sink(self, sink) -> None:
        self._elog.add_sink(sink)

    def bind_tracer(self, tracer) -> None:
        """Span-correlate report events: records emitted inside a span
        of ``tracer`` carry its id (the same run-id/span-id identity
        the campaign service logs — one scraper joins both)."""
        self._tracer = tracer

    def log(self, kind: str, **kw) -> None:
        span = (self._tracer.current_span_id()
                if self._tracer is not None else None)
        self._elog.emit(kind, span=span, **kw)

    def to_record(self) -> Dict:
        return dataclasses.asdict(self)

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_record(), f, indent=1)


def _current_config(dd) -> StepConfig:
    return StepConfig(pick_method(dd.methods), dd.exchange_every)


class _ResilientRun:
    """One ``run_resilient`` invocation (state bundled for clarity)."""

    def __init__(self, dd, step_fn, n_steps, policy, ckpt_dir, faults,
                 rebuild, extra_fn, on_restore, fields_fn,
                 pre_checkpoint, make_segment=None,
                 sentinel_factory=None, model_step_seconds=None,
                 model_bytes_per_step=None, perf_entry=None):
        self.dd = dd
        self.step_fn = step_fn
        self.n_steps = int(n_steps)
        self.policy = policy or ResiliencePolicy()
        self.ckpt_dir = ckpt_dir
        self.faults = faults
        self.rebuild = rebuild
        self.extra_fn = extra_fn
        self.on_restore = on_restore
        self.fields_fn = fields_fn
        self.pre_checkpoint = pre_checkpoint
        #: megastep factory: make_segment(k, probe_every, metrics) ->
        #: Segment or None (parallel/megastep.py); when present and
        #: policy.fuse_segments, the loop dispatches ONE fused program
        #: per health boundary instead of one jitted step per iteration
        self.make_segment = make_segment
        self._fused = (make_segment is not None
                       and self.policy.fuse_segments)
        #: one async checkpoint in flight: (step, field copies, extras)
        self._pending_save = None
        #: recompile watchdog (analysis/recompile.py): armed via
        #: STENCIL_ASSERT_SINGLE_COMPILE=1, raises if a fused segment
        #: program re-traces between dispatches
        self._compile_guard = (
            SingleCompileGuard()
            if os.environ.get(ASSERT_SINGLE_COMPILE_ENV) == "1"
            else None)
        #: custom sentinel builder (models whose health state is wider
        #: than dd.curr — e.g. the PIC particle lanes with the in-graph
        #: overflow column — supply one; step-metrics riding/rebasing
        #: is then the factory's business, not the driver's)
        self.sentinel_factory = sentinel_factory
        self.report = ResilienceReport()
        if faults is not None:
            faults.bind(self.report.log)
        self.sentinel = self._make_sentinel(dd)
        self.step = 0
        self.attempts = 0
        self.last_saved: Optional[int] = None
        self.ladder: Optional[List[StepConfig]] = None
        self._preempt = False
        # run-loop metrics (stable names, README "Observability"):
        # exported through the process-default telemetry registry
        from ..telemetry import get_registry, get_tracer
        reg = get_registry()
        self._tracer = get_tracer()
        # report events carry the span id of the enclosing run-loop
        # span, mirroring the service's span-correlated event log
        self.report.bind_tracer(self._tracer)
        self._m_steps = reg.counter(
            "stencil_run_steps_total",
            "steps advanced by resilient run loops — steps inside a "
            "fused megastep each count (one count per STEP, never per "
            "dispatch); replayed rollback windows included (work done, "
            "not net progress)")
        self._m_rollbacks = reg.counter(
            "stencil_run_rollbacks_total",
            "sentinel-tripped rollbacks")
        self._m_save_retries = reg.counter(
            "stencil_run_save_retries_total",
            "transient checkpoint-save retries")
        self._m_checkpoints = reg.counter(
            "stencil_run_checkpoints_total", "checkpoints written")
        self._m_degradations = reg.counter(
            "stencil_run_degradations_total",
            "configuration degradations taken")
        self._m_steps_per_s = reg.gauge(
            "stencil_run_steps_per_s",
            "steps/s of the last resilient run")
        self._m_bytes_per_step = reg.gauge(
            "stencil_run_bytes_per_step",
            "amortized exchange B/step (source=model: the analytic "
            "model the HLO cross-check pins; source=probe: harvested "
            "from the in-graph probe counters)")
        self._m_fused_dispatch = reg.counter(
            "stencil_run_fused_dispatch_total",
            "compiled-program dispatches by the resilient run loops, "
            "labeled fused=true (one megastep per count, covering k "
            "steps) or fused=false (one stepwise step dispatch per "
            "count) — a fleet reads the false series to see which "
            "campaigns still run stepwise and the fused_decline "
            "events to learn why")
        # seed the unlabeled counters so the exported surface carries
        # an explicit 0 baseline from birth (prometheus_client
        # semantics); "== 0" assertions then test a series that exists
        for c in (self._m_steps, self._m_rollbacks,
                  self._m_save_retries, self._m_checkpoints,
                  self._m_degradations):
            c.inc(0)
        for fused in ("true", "false"):
            self._m_fused_dispatch.inc(0, fused=fused)
        # performance observatory: model-vs-measured attribution of
        # every dispatch (observatory/attribution.py) and the bounded
        # flight recorder (observatory/recorder.py). The attributed
        # program is the SAME compiled fn — attribution is a host-side
        # wall clock; the observatory.attribution.* registry targets
        # pin the HLO identity
        self._perf_entry = perf_entry or "resilience"
        # the fused/stepwise verdict in the report: campaigns that run
        # stepwise must say so (and why) instead of silently falling
        # back — ResilienceReport.fused + fused_decline_reason + the
        # fused_decline event (a declining make_segment adds its own
        # reason at the first dispatch attempt)
        self.report.fused = self._fused
        if not self._fused:
            from ..parallel.megastep import (DECLINE_NO_FACTORY,
                                             DECLINE_POLICY_DISABLED)
            if make_segment is not None:
                self._note_fused_decline(
                    "fuse_segments disabled by policy",
                    code=DECLINE_POLICY_DISABLED)
            else:
                self._note_fused_decline(
                    "engine provides no fused-segment factory",
                    code=DECLINE_NO_FACTORY)
        self._model_step_seconds = model_step_seconds
        self._model_bytes_per_step = model_bytes_per_step
        self.attributor = (self._make_attributor()
                           if self.policy.attribute_perf else None)
        #: stepwise attribution window (accumulated dispatch seconds +
        #: base step): the stepwise loop attributes one
        #: check_every-sized WINDOW per observation — only step_fn
        #: dispatch time plus ONE fence at the health boundary is
        #: timed (blocking checkpoint saves, probe polls, and fault
        #: host work between steps are excluded, and the async-
        #: readback design of the stepwise loop survives attribution)
        self._att_window_s = 0.0
        self._att_window_base = None
        from ..observatory.recorder import ENV_FLIGHT_DIR, FlightRecorder
        self._flight_dir = (self.policy.flight_recorder_dir
                            or os.environ.get(ENV_FLIGHT_DIR) or None)
        self.flight = None
        if self._flight_dir:
            self.flight = FlightRecorder(run_id=self.report.run_id,
                                         registry=reg,
                                         tracer=self._tracer)
            self.report.add_sink(self.flight)
            # the attributor (built above) classified the link map
            # before the recorder existed — arm the black box with it
            self.flight.set_linkmap(getattr(self, "_link_summary",
                                            None))

    def _make_sentinel(self, dd,
                       rebase_step: Optional[int] = None,
                       prev=None) -> HealthSentinel:
        """A sentinel whose probe also carries the telemetry step
        metrics (sub-steps + model-exact wire bytes) on its ONE
        all-reduce — when the domain prices its exchange; plain
        otherwise. A degradation rebuild rebases the byte counter at
        ``rebase_step`` (the restore anchor, not the trip step: the
        rolled-back window re-executes under the NEW configuration and
        must be priced at its rate) so the new configuration's price
        applies only to steps it actually runs, never retroactively to
        traffic already sent. ``prev`` overrides the metrics block the
        rebase derives from (the finalize-after-restore path must
        rebase from the PRE-degrade block, not compound the
        provisional rebase)."""
        if self.sentinel_factory is not None:
            self._step_metrics = None
            return self.sentinel_factory(dd)
        from ..telemetry.probe import step_metrics_for
        if prev is None:
            prev = getattr(self, "_step_metrics", None)
        if prev is not None:
            if rebase_step is None:
                rebase_step = getattr(self, "step", 0)
            try:
                self._step_metrics = prev.rebased(dd, rebase_step)
            except Exception:  # noqa: BLE001 - new config unpriceable
                self._step_metrics = step_metrics_for(dd)
        else:
            self._step_metrics = step_metrics_for(dd)
        return HealthSentinel(dd, window=self.policy.window,
                              growth_factor=self.policy.growth_factor,
                              metrics=self._step_metrics)

    # -- performance observatory ----------------------------------------
    def _make_attributor(self):
        """A :class:`~stencil_tpu.observatory.PerfAttributor` for the
        CURRENT engine configuration, or None when no calibrated price
        exists (unsharded mesh, unpriceable geometry). The model price
        is the caller's override (PIC passes its migration+sweep
        figure) on the first build; a degradation rebuild re-derives
        from the rebuilt domain — the old figure priced a dead
        configuration."""
        from ..observatory.attribution import (PerfAttributor,
                                               model_step_seconds_for)
        model = self._model_step_seconds
        if model is None:
            model = model_step_seconds_for(self.dd)
        if not model:
            return None
        cfg = _current_config(self.dd)
        plan = getattr(self.dd, "plan", None)
        p = self.policy
        nbytes = self._model_bytes_per_step
        if nbytes is None:
            nbytes = (self._step_metrics.bytes_per_step
                      if getattr(self, "_step_metrics", None) is not None
                      else 0.0)
        # per-link attribution (observatory/linkmap.py): the modeled
        # traffic matrix classified against the deployed device order,
        # exported as stencil_link_bytes_per_step /
        # stencil_link_utilization_ratio next to the error ratio; the
        # flight recorder carries the same snapshot in incident dumps
        from ..observatory.linkmap import link_attribution_for
        link = link_attribution_for(self.dd)
        self._link_summary = link["summary"] if link else None
        if getattr(self, "flight", None) is not None:
            self.flight.set_linkmap(self._link_summary)
        return PerfAttributor(
            entry=self._perf_entry, method=cfg.method.name,
            exchange_every=cfg.exchange_every,
            model_step_seconds=model,
            model_bytes_per_step=float(nbytes),
            tolerance=p.drift_tolerance, window=p.drift_window,
            warmup=1,  # the first dispatch pays XLA compilation
            emit=self.report.log,
            on_drift=(self._on_perf_drift if p.retune_on_drift
                      else None),
            link_bytes_per_step=(link["bytes_per_step"] if link
                                 else None),
            link_peak_bytes_per_s=(link["peak_bytes_per_s"] if link
                                   else None),
            fingerprint=(plan.fingerprint if plan is not None else None))

    def _on_perf_drift(self, attrs: Dict) -> None:
        """``retune_on_drift``: the plan whose prediction the machine
        stopped matching is stale evidence — drop its plan-cache record
        so the next tune re-measures instead of serving the hit
        (shared hook: ``observatory.make_drift_invalidator``)."""
        from ..observatory.attribution import make_drift_invalidator
        make_drift_invalidator(self.policy.plan_cache_path,
                               self.report.log)(attrs)

    def _block_fields(self) -> None:
        import jax

        jax.block_until_ready(self._fields())

    def _attributed(self, k: int):
        """The timing context for one dispatch of ``k`` steps (a
        no-op when attribution is off/unpriceable)."""
        if self.attributor is None:
            return contextlib.nullcontext()
        return self.attributor.dispatch(k, self._block_fields,
                                        step=self.step + k)

    def _flight_dump(self, reason: str, **attrs) -> Optional[str]:
        from ..observatory.recorder import safe_dump
        return safe_dump(self.flight, self._flight_dir, reason,
                         step=self.step, **attrs)

    # -- helpers --------------------------------------------------------
    def _fields(self):
        return self.fields_fn() if self.fields_fn is not None \
            else self.dd.curr

    _UNSET = object()

    def _save(self, preempted: bool = False, fields=None,
              extra=_UNSET, at_step: Optional[int] = None) -> None:
        if fields is None and self.pre_checkpoint is not None:
            self.pre_checkpoint()
        if extra is self._UNSET:
            extra = self.extra_fn() if self.extra_fn is not None \
                else None
        step = self.step if at_step is None else int(at_step)
        meta_extra = {"preempted": preempted,
                      "completed_steps": step,
                      "config": _current_config(self.dd).key()}

        def attempt():
            if self.faults is not None:
                self.faults.maybe_fail_save(step)
            # attempts=1: THIS retry loop (policy clock, event-logged)
            # is the only one — no hidden nested retries inside
            save_domain(self.dd, self.ckpt_dir, step, extra=extra,
                        max_to_keep=self.policy.max_to_keep,
                        meta_extra=meta_extra, attempts=1,
                        fields=fields)

        def on_retry(k, e, delay):
            self.report.save_retries += 1
            self._m_save_retries.inc()
            self.report.log("save_retry", step=step, attempt=k,
                            error=f"{type(e).__name__}: {e}",
                            delay=delay)

        with self._tracer.span("checkpoint", step=step,
                               preempted=preempted):
            retry(attempt, attempts=self.policy.save_attempts,
                  base_delay=self.policy.base_delay,
                  retriable=(OSError,),
                  sleep=self.policy.sleep, on_retry=on_retry)
        self._m_checkpoints.inc()
        if self.faults is not None:
            self.faults.after_save(self.ckpt_dir, step)
        self.last_saved = step
        # a successful checkpoint is verified-healthy progress: bound
        # retries per INCIDENT, not per configuration lifetime —
        # independent transient faults days apart must not accumulate
        # toward forced degradation
        self.attempts = 0
        self.report.log("checkpoint", step=step, preempted=preempted)

    # -- async checkpoint offload (megastep mode) -----------------------
    def _save_async(self) -> None:
        """Enqueue a checkpoint of the CURRENT state without stalling
        the step pipeline: device copies of the fields (cheap, ride the
        device queue) are taken at the segment boundary so the live
        buffers can be donated to the next megastep; the orbax write
        runs once the copies report ``is_ready`` (polled each loop
        turn) — the EnsembleSnapshot pattern applied to checkpoints.
        Exactly one save is in flight; ordering is preserved by
        flushing before the next enqueue, any restore, preemption, and
        loop end."""
        import jax.numpy as jnp

        self._flush_pending_save()
        if self.pre_checkpoint is not None:
            self.pre_checkpoint()
        fields = {q: jnp.copy(v) for q, v in self.dd.curr.items()}
        extra = self.extra_fn() if self.extra_fn is not None else None
        if extra:
            extra = {k: jnp.copy(v) for k, v in extra.items()}
        self._pending_save = (self.step, fields, extra)

    def _poll_pending_save(self, block: bool = False) -> None:
        from .health import _is_ready
        ps = self._pending_save
        if ps is None:
            return
        step, fields, extra = ps
        if not block and not all(_is_ready(v)
                                 for v in fields.values()):
            return
        self._pending_save = None
        self._save(fields=fields, extra=extra, at_step=step)

    def _flush_pending_save(self) -> None:
        self._poll_pending_save(block=True)

    def _drain_probe(self) -> List[HealthStats]:
        """Blocking health verdict on the CURRENT state (used at
        checkpoint boundaries and loop end). Reuses an in-flight probe
        of this step — or a fused-trace row of it already harvested
        clean this turn — rather than paying a duplicate reduction."""
        if not self.sentinel.has_pending(self.step) and \
                getattr(self, "_last_clean_health", None) != self.step:
            self.sentinel.probe(self._fields(), self.step)
        results = self.sentinel.poll(block=True)
        self._observe_probes(results)
        for s in results:
            if not s.tripped:
                self._last_clean_health = s.step
        return [s for s in results if s.tripped]

    def _observe_probes(self, results: List[HealthStats]) -> None:
        """Export the in-graph counters the probes carried: the
        probe-observed amortized B/step next to the model's figure.
        They agree while one configuration runs (the probe's counter
        IS the model-exact byte price — the costmodel checker pins it
        against HLO); after a degradation the probe figure is the
        campaign-average across the configurations actually run."""
        if self.flight is not None:
            for stats in results:
                self.flight.record_probe(stats.to_record())
        if self._step_metrics is None:
            return
        for stats in results:
            if not stats.metrics:
                continue
            decoded = self._step_metrics.decode(stats.metrics)
            self._m_bytes_per_step.set(decoded["bytes_per_step_probe"],
                                       source="probe")

    def _restore(self) -> None:
        with self._tracer.span("restore"):
            step, extras = restore_domain(self.dd, self.ckpt_dir)
        if self.on_restore is not None:
            self.on_restore(extras)
        self.step = step
        pre_degrade = getattr(self, "_rebase_from", None)
        if pre_degrade is not None:
            # finalize the post-degradation byte rebase at the step the
            # restore ACTUALLY landed on: restore_domain may have
            # walked back past a corrupt last_saved checkpoint, and the
            # whole re-executed window must be priced at the degraded
            # configuration's rate
            self._rebase_from = None
            self.sentinel = self._make_sentinel(
                self.dd, rebase_step=step, prev=pre_degrade)
        self.sentinel.reset()
        self._last_clean_health = None
        # a rolled-back window is replay, not fresh progress: never
        # attribute wall time that spans the restore
        self._att_window_base = None
        self.report.log("restored", step=step)

    def _handle_trip(self, tripped: List[HealthStats]) -> None:
        # an in-flight async checkpoint (healthy by construction: it was
        # enqueued only after a clean blocking drain) completes FIRST —
        # before the attempt counter moves — so it can anchor the
        # rollback AND its attempts-reset lands where the stepwise
        # ordering puts it (the save preceded the faulting steps; it
        # must not forgive the attempt recorded for THIS trip)
        self._flush_pending_save()
        stats = tripped[0]
        self.report.rollbacks += 1
        self._m_rollbacks.inc()
        self.attempts += 1
        self.report.log("sentinel_tripped", step=stats.step,
                        reason=stats.reason,
                        stats=stats.to_record(),
                        attempt=self.attempts)
        LOG_WARN(f"health sentinel tripped at step {stats.step}: "
                 f"{stats.reason} (attempt {self.attempts}/"
                 f"{self.policy.max_retries})")
        if self.ckpt_dir is None:
            raise ResilienceError(
                f"sentinel tripped at step {stats.step} "
                f"({stats.reason}) and no ckpt_dir was given — "
                f"nothing to roll back to")
        if self.attempts > self.policy.max_retries:
            self._degrade_or_die(stats)  # resets attempts to 0
        self.policy.sleep(self.policy.base_delay
                          * (2 ** max(self.attempts - 1, 0)))
        self._restore()
        # the black box captures the WHOLE incident — trip, any
        # degradation, and the rollback it resolved into
        self._flight_dump("sentinel_trip", trip_step=stats.step,
                          trip_reason=stats.reason)

    def _degrade_or_die(self, stats: HealthStats) -> None:
        if self.ladder is None:
            cfg = _current_config(self.dd)
            self.ladder = degradation_ladder(cfg.method,
                                             cfg.exchange_every)
        # walk rungs until one actually realizes: domain feasibility
        # (uneven shards, Boundary.NONE, temporal-depth limits) surfaces
        # only in the constructor — an infeasible rung is skipped, never
        # allowed to kill the recovery with a raw NotImplementedError
        while (self.policy.degrade and self.rebuild is not None
               and self.ladder):
            cfg = self.ladder.pop(0)
            LOG_WARN(f"degrading configuration to {cfg.key()} after "
                     f"repeated failures")
            try:
                built = self.rebuild(cfg)
                # a 3-tuple rebuild also rebuilds the fused-segment
                # factory (megastep mode): the degraded engine's
                # segments, not the dead configuration's, serve from
                # here on; a 2-tuple (legacy) drops to the stepwise
                # loop — never dispatch a stale fused program
                if len(built) == 3:
                    self.dd, self.step_fn, self.make_segment = built
                    self._fused = (self.make_segment is not None
                                   and self.policy.fuse_segments)
                else:
                    self.dd, self.step_fn = built
                    if self._fused:
                        LOG_WARN("rebuild() returned no segment "
                                 "factory; continuing stepwise")
                        self._fused = False
                        # the fallback is a reported fact, not a
                        # silence: fused: false + reason + event
                        from ..parallel.megastep import \
                            DECLINE_REBUILD_NO_FACTORY
                        self._note_fused_decline(
                            "rebuild() returned no segment factory "
                            "after degradation",
                            code=DECLINE_REBUILD_NO_FACTORY)
            except (NotImplementedError, ValueError) as e:
                self.report.log("degrade_rung_infeasible",
                                config=cfg.key(),
                                error=f"{type(e).__name__}: {e}")
                LOG_WARN(f"degradation rung {cfg.key()} is infeasible "
                         f"for this domain ({e}); trying the next")
                continue
            # rebase at the restore anchor: _handle_trip restores right
            # after this, and every step past the restored checkpoint
            # re-runs under the degraded configuration's byte price.
            # last_saved is the provisional anchor; _restore finalizes
            # it from the PRE-degrade metrics stashed here, because a
            # corrupt last_saved checkpoint can make the restore walk
            # back further
            self._rebase_from = self._step_metrics
            anchor = (self.last_saved if self.last_saved is not None
                      else getattr(self, "step", 0))
            self.sentinel = self._make_sentinel(self.dd,
                                                rebase_step=anchor)
            if self._step_metrics is not None:
                # the degraded configuration has a new per-step byte
                # price — keep the exported model figure current so the
                # model-vs-probe comparison stays honest mid-run
                self._m_bytes_per_step.set(
                    self._step_metrics.bytes_per_step, source="model")
            self.attempts = 0
            self.report.degradations.append(cfg.key())
            self._m_degradations.inc()
            self.report.log("degraded", config=cfg.key())
            if self.attributor is not None:
                # the degraded engine has a new model price and labels;
                # the caller's override (if any) priced the dead config
                self._model_step_seconds = None
                self._model_bytes_per_step = None
                self.attributor = self._make_attributor()
                self._att_window_base = None
            self._flight_dump("degraded", config=cfg.key())
            return
        raise ResilienceError(
            f"retries exhausted ({self.policy.max_retries}) at "
            f"step {stats.step}: {stats.reason}; no degradation "
            f"available")

    # -- megastep segmentation ------------------------------------------
    def _note_fused_decline(self, reason: str, model: str = "",
                            path: str = "", code: str = "") -> None:
        """Make a stepwise fallback VISIBLE: the report says
        ``fused: false`` with the reason AND its vocabulary code
        (``megastep.DECLINE_*``), the event log carries a
        ``fused_decline`` record, and the fleet counter's
        ``fused=false`` series accumulates the stepwise dispatches."""
        from ..parallel.megastep import DECLINE_NO_FACTORY

        self.report.fused = False
        self.report.fused_decline_reason = reason
        self.report.fused_decline_code = code or DECLINE_NO_FACTORY
        self.report.log("fused_decline",
                        model=model or self._perf_entry,
                        path=path, reason=reason,
                        code=self.report.fused_decline_code)

    def _next_seg_len(self) -> int:
        """Steps until the next host boundary: campaign end, the
        check_every health boundary, a checkpoint boundary, a scheduled
        host fault, or the unroll cap — the fused segment runs exactly
        that far in ONE dispatch."""
        from ..parallel.megastep import MAX_UNROLL
        p = self.policy
        cands = [self.n_steps - self.step, MAX_UNROLL]
        ce = max(int(p.check_every), 1)
        cands.append(ce - self.step % ce)
        if self.ckpt_dir is not None and p.ckpt_every > 0:
            cands.append(p.ckpt_every - self.step % p.ckpt_every)
        if self.faults is not None:
            nf = self.faults.next_host_step(self.step)
            if nf is not None:
                cands.append(nf - self.step)
        return max(1, min(c for c in cands if c > 0))

    def _dispatch_segment(self) -> bool:
        """Advance one fused megastep (ONE compiled dispatch for the
        whole sub-check_every span, probe trace in-graph). Returns
        False when the current engine configuration has no fused
        segment — the caller re-enters the loop stepwise."""
        k = self._next_seg_len()
        seg = self.make_segment(k, self.policy.probe_every,
                                self._step_metrics)
        if not seg:
            # a SegmentDecline (or legacy None): record the fallback
            # with its reason — fused: false in the report, a
            # fused_decline event, and the fused=false counter series
            reason = getattr(seg, "reason",
                             "engine has no fused-segment support for "
                             "this configuration")
            self._note_fused_decline(
                reason, model=getattr(seg, "model", ""),
                path=getattr(seg, "path", ""),
                code=getattr(seg, "code", ""))
            LOG_WARN(f"no fused-segment support for this configuration "
                     f"({reason}); continuing with the stepwise "
                     f"dispatch loop")
            self._fused = False
            return False
        base = self.step
        with self._tracer.span("megastep", steps=k, step=base):
            # one Perfetto box per COMPILED PROGRAM (the megastep span
            # also covers guard/bookkeeping overhead around it), timed
            # by the attributor — model-vs-measured attribution is a
            # host wall clock; the dispatched program is unchanged
            with self._tracer.span("segment.dispatch", k=k,
                                   check_every=self.policy.check_every,
                                   entry=self._perf_entry):
                with self._attributed(k):
                    # the hot-loop dataflow contract, enforced at
                    # runtime: the fused dispatch moves NOTHING
                    # implicitly between host and device (the probe
                    # trace stays on device, the metric base vec is an
                    # explicit replicated device_put) — see
                    # analysis/transfer.py; STENCIL_ALLOW_TRANSFERS=1
                    # opts out
                    with hot_loop_transfer_guard():
                        trace = seg.run(base)
        if self._compile_guard is not None:
            self._compile_guard.observe(seg.fn, "megastep segment")
        self.step += k
        self.report.steps = self.step
        self._m_steps.inc(k)
        self._m_fused_dispatch.inc(fused="true")
        self.sentinel.observe_segment(trace.array, trace.abs_steps)
        return True

    # -- the loop -------------------------------------------------------
    def run(self) -> ResilienceReport:
        try:
            with self._tracer.span("resilience.run",
                                   run=self.report.run_id,
                                   n_steps=self.n_steps):
                return self._run()
        except Exception as e:
            # unhandled dispatch/recovery error: the black box is the
            # post-mortem (the raise still propagates unchanged)
            self._flight_dump("unhandled_error",
                              error=f"{type(e).__name__}: {e}")
            raise

    def _run(self) -> ResilienceReport:
        policy = self.policy
        if self._step_metrics is not None:
            self._m_bytes_per_step.set(
                self._step_metrics.bytes_per_step, source="model")
        t_start = time.perf_counter()
        steps_at_start = self.step
        if self.ckpt_dir is not None:
            try:
                self._restore()
                self.report.resumed_from = self.step
                LOG_INFO(f"resuming from checkpoint step {self.step}")
            except FileNotFoundError:
                self._save()  # step 0: the rollback anchor
        handler_installed = False
        prev_handler = None
        if threading.current_thread() is threading.main_thread():
            prev_handler = signal.signal(
                signal.SIGTERM, lambda *_: setattr(self, "_preempt",
                                                   True))
            handler_installed = True
        try:
            while True:
                self._poll_pending_save()
                if self._preempt:
                    self._flush_pending_save()
                    # black box BEFORE the preemption checkpoint: if
                    # the final save itself dies, the incident record
                    # already exists on disk
                    self._flight_dump("preempt")
                    if self.ckpt_dir is not None:
                        # same invariant as periodic checkpoints:
                        # poisoned state must never be persisted — if
                        # the drain trips, skip the save and let the
                        # last good checkpoint anchor the resume
                        tripped = self._drain_probe()
                        if tripped:
                            self.report.log(
                                "preempt_checkpoint_skipped",
                                step=self.step,
                                reason=tripped[0].reason)
                            LOG_WARN(
                                f"preempted at step {self.step} with "
                                f"unhealthy state ({tripped[0].reason})"
                                f"; NOT checkpointing it — resume will "
                                f"restore step {self.last_saved}")
                        else:
                            self._save(preempted=True)
                    self.report.preempted = True
                    self.report.log("preempted", step=self.step)
                    LOG_WARN(f"preempted at step {self.step}; exiting "
                             f"cleanly")
                    break
                if self.step >= self.n_steps:
                    self._flush_pending_save()
                    if self.last_saved == self.step:
                        break  # this step already drained + saved
                    tripped = self._drain_probe()
                    if tripped:
                        self._handle_trip(tripped)
                        continue
                    if self.ckpt_dir is not None:
                        self._save()
                    break
                if self._fused:
                    # megastep mode: ONE compiled dispatch to the next
                    # host boundary; the probe trace rides in-graph
                    if not self._dispatch_segment():
                        continue  # no fused support: retry stepwise
                    if self.faults is not None:
                        mutated = self.faults.on_step(
                            self.dd, self.step, self._fields())
                        if mutated:
                            # the in-graph trace predates the host
                            # injection: re-probe the poisoned fields
                            # so detection matches the stepwise loop —
                            # BEFORE any preempt drain can mistake the
                            # stale clean trace row for current health
                            self._last_clean_health = None
                            self.sentinel.probe(self._fields(),
                                                self.step)
                        if self._preempt:
                            continue  # SIGTERM landed at the boundary
                else:
                    att = self.attributor
                    if att is not None:
                        if self._att_window_base is None:
                            self._att_window_base = self.step
                            self._att_window_s = 0.0
                        t0 = time.perf_counter()
                        self.step_fn()
                        self._att_window_s += time.perf_counter() - t0
                    else:
                        self.step_fn()
                    self.step += 1
                    self.report.steps = self.step
                    self._m_steps.inc()
                    self._m_fused_dispatch.inc(fused="false")
                    if att is not None \
                            and self.step % policy.check_every == 0:
                        # boundary-amortized: the accumulated step
                        # dispatch time plus ONE fence per check_every
                        # window (the fused path's k-step
                        # amortization, mirrored) — never a fence per
                        # step, and never the saves/probes/fault host
                        # work that run between steps
                        t0 = time.perf_counter()
                        self._block_fields()
                        self._att_window_s += time.perf_counter() - t0
                        att.observe(self.step - self._att_window_base,
                                    self._att_window_s, step=self.step)
                        self._att_window_base = None
                    if self.faults is not None:
                        # faults hit the LIVE fields — the same dict
                        # the sentinel probes (interior-resident fast
                        # paths keep their state outside dd.curr)
                        self.faults.on_step(self.dd, self.step,
                                            self._fields())
                    if self._preempt:
                        continue  # SIGTERM landed during the step
                    if self.step % policy.check_every == 0 and not (
                            self.ckpt_dir is not None
                            and self.step % policy.ckpt_every == 0):
                        # checkpoint boundaries probe via the blocking
                        # drain below — one reduction per step, not two
                        self.sentinel.probe(self._fields(), self.step)
                results = self.sentinel.poll()
                self._observe_probes(results)
                for s in results:
                    if not s.tripped:
                        self._last_clean_health = s.step
                tripped = [s for s in results if s.tripped]
                if tripped:
                    self._handle_trip(tripped)
                    continue
                ckpt_due = (self.ckpt_dir is not None
                            and self.step % policy.ckpt_every == 0)
                if ckpt_due:
                    tripped = self._drain_probe()
                    if tripped:
                        self._handle_trip(tripped)
                        continue
                    if self._fused:
                        # async host offload: the TPU starts the next
                        # segment while orbax drains boundary copies
                        self._save_async()
                    else:
                        self._save()
        finally:
            if handler_installed:
                signal.signal(signal.SIGTERM,
                              prev_handler if prev_handler is not None
                              else signal.SIG_DFL)
            if self._pending_save is not None:
                # best-effort durability on abnormal exits: never mask
                # the in-flight exception with a failing late save
                try:
                    self._flush_pending_save()
                except Exception as e:  # noqa: BLE001
                    LOG_WARN(f"in-flight checkpoint lost on exit: "
                             f"{type(e).__name__}: {e}")
        self.report.steps = self.step
        self.report.final_config = _current_config(self.dd).key()
        elapsed = time.perf_counter() - t_start
        # steps THIS invocation advanced (a resume starts mid-campaign)
        done = self.step - max(steps_at_start,
                               self.report.resumed_from or 0)
        if done > 0 and elapsed > 0:
            self._m_steps_per_s.set(done / elapsed)
        return self.report


def run_resilient(dd, step_fn: Callable[[], None], n_steps: int,
                  policy: Optional[ResiliencePolicy] = None,
                  ckpt_dir: Optional[str] = None,
                  faults: Optional[FaultPlan] = None,
                  rebuild: Optional[Callable] = None,
                  extra_fn: Optional[Callable[[], Optional[Dict]]] = None,
                  on_restore: Optional[Callable[[Dict], None]] = None,
                  fields_fn: Optional[Callable[[], Dict]] = None,
                  pre_checkpoint: Optional[Callable[[], None]] = None,
                  make_segment: Optional[Callable] = None,
                  sentinel_factory: Optional[Callable] = None,
                  model_step_seconds: Optional[float] = None,
                  model_bytes_per_step: Optional[float] = None,
                  perf_entry: Optional[str] = None
                  ) -> ResilienceReport:
    """Drive ``step_fn`` for ``n_steps`` steps with health sentinels,
    periodic integrity-checked checkpoints, rollback-retry recovery,
    optional configuration degradation, and clean SIGTERM preemption.

    ``dd``: the realized :class:`~stencil_tpu.distributed.
    DistributedDomain` whose ``curr`` fields ARE the run state.
    ``step_fn()``: advance the state by one step (e.g. a model's
    ``step`` bound method). ``ckpt_dir``: checkpoint directory; when
    None the sentinel still watches but a trip raises (watchdog-only
    mode). ``rebuild(config)``: re-realize the engine at a degraded
    :class:`StepConfig`, returning ``(dd, step_fn)`` — required for the
    degradation ladder. ``extra_fn``/``on_restore``: checkpoint and
    reinstall auxiliary state (RK accumulators). ``fields_fn``: the
    dict the sentinel probes (defaults to ``dd.curr``).
    ``pre_checkpoint``: flush hook run before every save (fast paths
    sync interior-resident state).

    ``make_segment(k, probe_every, metrics)``: the megastep factory
    (``parallel/megastep.py``) — when given (the model entry points
    pass theirs) and ``policy.fuse_segments`` is on (default), the loop
    dispatches ONE fused program per health boundary: ``k`` steps plus
    the in-graph probe trace, state donated end-to-end, checkpoints
    offloaded asynchronously from boundary copies. ``rebuild`` may
    return ``(dd, step_fn, make_segment)`` so a degradation rebuilds
    the fused segment too (a 2-tuple falls back to stepwise).

    ``sentinel_factory(dd)``: build the health sentinel instead of the
    driver's default ``HealthSentinel(dd, ...)`` — models whose live
    state is wider than the domain's registered fields (PIC probes the
    particle lanes and carries the in-graph migration-overflow column)
    supply one; telemetry step-metrics riding is then the factory's
    responsibility.

    ``model_step_seconds``/``model_bytes_per_step``/``perf_entry``:
    the performance observatory's attribution inputs — the calibrated
    cost-model prediction of seconds/step and modeled wire B/step
    (models whose wire bill the generic exchange model cannot see,
    like PIC's migration ring, pass their own; None derives both from
    ``dd``) and the ``entry`` label of the exported
    ``stencil_perf_model_error_ratio{entry,method,s}`` gauges.

    Returns a :class:`ResilienceReport`; if it says ``preempted``,
    rerun with the same ``ckpt_dir`` to resume. If a run was previously
    preempted mid-campaign, the same call resumes it automatically."""
    return _ResilientRun(dd, step_fn, n_steps, policy, ckpt_dir, faults,
                         rebuild, extra_fn, on_restore, fields_fn,
                         pre_checkpoint, make_segment=make_segment,
                         sentinel_factory=sentinel_factory,
                         model_step_seconds=model_step_seconds,
                         model_bytes_per_step=model_bytes_per_step,
                         perf_entry=perf_entry).run()
