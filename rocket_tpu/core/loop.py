"""Looper — the per-phase iteration loop (one per train/val/test phase).

Reference semantics (``rocket/core/loop.py``):

* ``set()`` infers the iteration count by summing child ``Dataset`` totals
  (``loop.py:113-125``), errors on infinite loops (``loop.py:48-51``), and
  publishes the loop contract ``attrs.looper = {repeats, state, terminate,
  tag}`` (``loop.py:53-58``);
* ``launch()`` shows a progress bar only on the local main process
  (``loop.py:75-79``), then per iteration clears ``attrs.batch``, runs the
  children as one dispatch wave, breaks on ``attrs.looper.terminate``
  (``loop.py:81-90``) and mirrors ``attrs.looper.state`` into the bar postfix;
* ``run_every`` gating skips whole epochs (``loop.py:34-39``); nested Loopers
  are forbidden (``loop.py:106-111``); stateful ``epoch_idx``/``batch_idx``
  (``loop.py:98-104``).

Substrate deviation (SURVEY.md §7): JAX has no ambient autograd mode, so the
reference's ``torch.set_grad_enabled(self._grad_enabled)`` (``loop.py:85``)
becomes an explicit ``attrs.mode = "train" | "eval"`` that Module / Loss /
Optimizer / Scheduler / Tracker / Dataset read from the bag.

Deliberate fixes: repeats are re-inferred every epoch (the reference leaves
``_repeats = -1`` after epoch one so later epochs never iterate,
``loop.py:95``), and ``batch_idx`` actually advances (dead state in the
reference, ``loop.py:103``).
"""

from __future__ import annotations

from typing import Iterable, Optional

import jax

from rocket_tpu.core.attributes import Attributes
from rocket_tpu.core.capsule import Capsule
from rocket_tpu.core.dispatcher import Dispatcher
from rocket_tpu.obs import spans

__all__ = ["Looper"]


class Looper(Dispatcher):
    """Drives its children for ``repeats`` iterations per epoch.

    Parameters
    ----------
    capsules:
        One iteration = one priority-ordered dispatch wave over these.
    tag:
        Phase name (``"train"``, ``"val"`` ...) — keys tracker scalars and the
        progress bar.
    grad_enabled:
        True -> ``attrs.mode = "train"`` (loss/optimizer/scheduler active);
        False -> ``attrs.mode = "eval"``. Name kept from the reference API.
    repeats:
        Explicit iteration count; if None it is inferred each epoch from child
        ``Dataset`` totals.
    run_every:
        Run this phase only on epochs where ``epoch_idx % run_every == 0``.
    """

    def __init__(
        self,
        capsules: Iterable[Capsule] = (),
        tag: str = "train",
        grad_enabled: bool = True,
        repeats: Optional[int] = None,
        run_every: int = 1,
        progress: bool = True,
        postfix_every: int = 1,
        statefull: bool = True,
        priority: int = 1000,
        runtime=None,
    ) -> None:
        super().__init__(capsules, statefull=statefull, priority=priority, runtime=runtime)
        if run_every < 1:
            raise RuntimeError(f"Looper: run_every must be >= 1, got {run_every}")
        self._tag = tag
        self._grad_enabled = grad_enabled
        self._explicit_repeats = repeats
        self._repeats: Optional[int] = repeats
        self._run_every = run_every
        self._progress = progress
        # Formatting the postfix reads device scalars (a host sync); throttle
        # it when benchmarking tight loops.
        self._postfix_every = max(1, postfix_every)
        self._epoch_idx = 0
        self._batch_idx = 0  # mid-epoch position, persisted for resume
        self._active = True  # run_every gate for the current epoch
        # First wave driven in THIS process (not checkpointed): that wave
        # traces+compiles the step, so telemetry classifies it "compile".
        self._warmed = False

    # -- properties --------------------------------------------------------

    @property
    def tag(self) -> str:
        return self._tag

    @property
    def mode(self) -> str:
        return "train" if self._grad_enabled else "eval"

    # -- guards ------------------------------------------------------------

    def guard(self, capsules: Iterable[Capsule]) -> None:
        super().guard(capsules)
        for capsule in capsules:
            if isinstance(capsule, Looper):
                raise RuntimeError(
                    "Looper: nested Loopers are forbidden (loop.py:106-111); "
                    "compose phases side by side under the Launcher."
                )

    def _gated(self, attrs: Attributes | None) -> bool:
        epoch = 0
        if attrs is not None and attrs.launcher is not None:
            epoch = attrs.launcher.epoch_idx or 0
        return epoch % self._run_every != 0

    # -- events ------------------------------------------------------------

    def set(self, attrs: Attributes | None = None) -> None:
        self._active = not self._gated(attrs)
        if not self._active:
            return
        attrs = Attributes() if attrs is None else attrs

        # Re-infer repeats every epoch unless explicitly pinned (fixes
        # the reference's one-epoch bug, loop.py:45-46,95).
        if self._explicit_repeats is None:
            self._repeats = self._infer_repeats()
        if self._repeats is None:
            raise RuntimeError(
                "Looper: cannot infer repeats — no child Dataset reports a "
                "finite total; pass repeats= explicitly (loop.py:48-51)."
            )

        attrs.mode = self.mode
        attrs.looper = Attributes(
            repeats=self._repeats,
            state=Attributes(),
            terminate=False,
            tag=self._tag,
        )
        super().set(attrs)

    def launch(self, attrs: Attributes | None = None) -> None:
        if not self._active:
            return
        attrs = Attributes() if attrs is None else attrs
        self.log_debug(f"launch: {self._repeats} iterations [{self._tag}]")

        bar = self._progress_bar()
        start = self._batch_idx  # >0 only on mid-epoch resume

        # Each iteration wave is the span `<tag>/wave` (rocket_tpu.obs.spans;
        # category "compile" for the first wave this process drives — that
        # wave traces+compiles the step — "step" after) with a
        # jax.profiler.StepTraceAnnotation inside it, so a device trace
        # shares the step boundaries: on under a profiler session or run
        # telemetry, the shared no-op otherwise. With telemetry the hang
        # watchdog is armed for exactly the duration of the loop with a
        # beat per completed wave. All of it is host bookkeeping — nothing
        # touches the device.
        telemetry = getattr(self._runtime, "telemetry", None)
        obs_on = telemetry is not None and telemetry.enabled
        # Resilience (rocket_tpu.resilience): the drain flag is polled at
        # every wave boundary — a SIGTERM lands mid-wave, the wave
        # finishes, and the NEXT boundary checkpoints + exits with the
        # drained code; the fault injector (ROCKET_TPU_FAULTS) fires its
        # scheduled kills/wedges here so the real loop path is what dies.
        drain = getattr(self._runtime, "drain", None)
        faults = getattr(self._runtime, "faults", None)
        if obs_on:
            telemetry.watchdog_arm()
        try:
            for it in range(start, self._repeats):
                if drain is not None and drain.requested:
                    self._drain_exit()
                if faults is not None:
                    faults.step_hook(self._tag, self._batch_idx)
                attrs.batch = None
                attrs.mode = self.mode
                # Strict mode clamps the iteration wave — the steady-state
                # hot path — under a full transfer guard: any IMPLICIT
                # host<->device transfer a capsule sneaks into the loop
                # (float(scalar), numpy into jit) raises at the offending
                # line. Explicit device_put/device_get stay legal. The
                # FIRST wave of the epoch runs unguarded: it compiles the
                # step, and loading the executable uploads its embedded
                # constants (an implicit H2D by design); from the second
                # wave on the shapes are stable — wrap padding guarantees
                # it — and everything implicit is a genuine leak.
                wave = (
                    telemetry.step_span(
                        self._tag, self._batch_idx,
                        cat=("step" if self._warmed else "compile"),
                    )
                    if telemetry is not None
                    else spans.OFF
                )
                with self._iteration_guard(warmup=(it == start)), wave:
                    Dispatcher.launch(self, attrs)
                self._warmed = True
                if obs_on:
                    telemetry.beat()
                if attrs.looper is not None and attrs.looper.terminate:
                    break
                self._batch_idx += 1
                if bar is not None:
                    bar.update(1)
                    if (
                        self._batch_idx % self._postfix_every == 0
                        and attrs.looper is not None
                        and attrs.looper.state
                    ):
                        # Deliberate, throttled sync: formatting the postfix
                        # reads device scalars. device_get keeps it an
                        # EXPLICIT transfer (strict-mode transfer_guard
                        # allows it); postfix_every bounds the cost.
                        bar.set_postfix(
                            {k: f"{float(jax.device_get(v)):.4g}"  # rocketlint: disable=RKT103,RKT106
                             for k, v in attrs.looper.state.items()},
                            refresh=False,
                        )
            health = getattr(self._runtime, "health", None)
            if health is not None and health.enabled:
                # Epoch end: decode the health words still inside their
                # fetch lag (one batched explicit device_get) so an
                # anomaly in the final steps acts THIS epoch — under
                # dump_and_halt it raises here, not at teardown.
                health.drain()
        except Exception as exc:
            # Black-box forensics: an exception escaping the step loop is
            # exactly the "dead process with no trail" case — dump the
            # flight recorder (sentinel history, spans tail, emergency
            # checkpoint) before the stack unwinds. HealthAnomalyError
            # already dumped inside the anomaly policy; the telemetry
            # hook skips it. Re-raised unchanged either way.
            if telemetry is not None:
                telemetry.exception_dump(
                    exc, tag=self._tag, epoch_idx=self._epoch_idx,
                    batch_idx=self._batch_idx,
                )
            raise
        finally:
            if obs_on:
                telemetry.watchdog_disarm()
            if bar is not None:
                bar.close()

    def reset(self, attrs: Attributes | None = None) -> None:
        if not self._active:
            return
        self._epoch_idx += 1
        self._batch_idx = 0
        # Children reset first — epoch-end publishers (Metric.reset, the
        # Tracker's final flush) still need the loop contract and its tag.
        super().reset(attrs)
        if attrs is not None:
            attrs.mode = None
            attrs.looper = None

    # -- helpers -----------------------------------------------------------

    def _drain_exit(self) -> None:
        """Honor a drain request at a wave boundary: write a synchronous
        drain checkpoint through the first Checkpointer in this phase and
        raise :class:`~rocket_tpu.resilience.faults.GracefulDrain` — a
        ``SystemExit`` carrying the distinguished drained exit code, so
        the process unwinds through every ``finally`` (bar close, watchdog
        disarm, Launcher destroy, telemetry flush) and the supervisor sees
        a clean stop. The crash-forensics ``except Exception`` below does
        not catch it: a drain is not a failure."""
        from rocket_tpu.core.checkpoint import Checkpointer
        from rocket_tpu.resilience.faults import GracefulDrain

        reason = self._runtime.drain.reason or "drain"
        self.log_info(
            f"drain requested ({reason}) — checkpointing and exiting "
            f"[{self._tag}, batch {self._batch_idx}]"
        )
        path = None
        # Prefer this phase's own Checkpointer (its step index matches the
        # waves being drained); fall back to the runtime-wide registry so
        # a SIGTERM landing during a checkpointer-less phase (eval) still
        # saves through the sibling train phase's Checkpointer.
        checkpointers = self.find(Checkpointer) or [
            c for c in getattr(self._runtime, "checkpointers", ())
        ]
        if checkpointers:
            path = checkpointers[0].save_drain()
        else:
            self.log_warning(
                "drain: no Checkpointer in this run — exiting without an "
                "emergency checkpoint"
            )
        telemetry = getattr(self._runtime, "telemetry", None)
        if telemetry is not None and telemetry.enabled:
            telemetry.registry.counter("resilience/drains").inc()
        raise GracefulDrain(checkpoint=path, reason=reason)

    def _iteration_guard(self, warmup: bool = False):
        """Transfer guard for one iteration wave (strict mode), else a
        no-op context."""
        import contextlib

        if (
            not warmup
            and self._runtime is not None
            and self._runtime.strict.enabled
        ):
            return jax.transfer_guard(self._runtime.strict.transfer_guard)
        return contextlib.nullcontext()

    def _infer_repeats(self) -> Optional[int]:
        """Sum child Dataset totals (loop.py:113-125)."""
        from rocket_tpu.core.dataset import Dataset

        totals = [d.total for d in self.find(Dataset)]
        totals = [t for t in totals if t is not None]
        return sum(totals) if totals else None

    def _progress_bar(self):
        """tqdm on the local main process only (loop.py:75-79)."""
        if not self._progress:
            return None
        if self._runtime is not None and not self._runtime.is_local_main_process:
            return None
        try:
            from tqdm import tqdm
        except ImportError:  # pragma: no cover
            return None
        return tqdm(
            total=self._repeats,
            initial=self._batch_idx,
            desc=self._tag,
            leave=True,
            dynamic_ncols=True,
        )

    # -- checkpoint state --------------------------------------------------

    def state_dict(self) -> dict:
        return {"epoch_idx": self._epoch_idx, "batch_idx": self._batch_idx}

    def load_state_dict(self, state: dict) -> None:
        self._epoch_idx = int(state["epoch_idx"])
        self._batch_idx = int(state["batch_idx"])
