"""Backend selection: one rule for the one installation there is.

The chip is local: JAX either initializes it in this process or says why
not (the reference's stance too — a single local GPU whose CUDA init
works or aborts, reference MapReduce/src/main.cu:393).  So selection is
three lines of public jax config, and nothing here probes, retries,
caches a verdict or falls back:

  * ``cpu``  — pin ``jax_platforms=cpu``.
  * ``tpu``  — initialize jax here and raise unless the first device is
    a TPU.  An ambient ``JAX_PLATFORMS=cpu`` therefore FAILS a tpu
    request; it is never silently overridden.
  * ``auto`` — take whatever jax initializes (an explicit
    ``JAX_PLATFORMS`` is jax's own input and is honoured as such).

``select_backend()`` must run before anything else touches a jax backend
(``jax.devices()``, ``jnp.asarray`` on a concrete value, jit execution):
a platform pin cannot move a backend that is already initialized.

A chip belongs to ONE process at a time.  A parent that has selected the
TPU holds it; python children that need jax must be pinned to the CPU
(``env=`` with ``JAX_PLATFORMS=cpu``, analyzer rule R006) or given a
chip of their own.

``CircuitBreaker`` / ``guarded_dispatch`` / ``cpu_fallback_device`` are
an opt-in ``breaker=`` argument of the checkpointed engine run — not on
the selection path above.
"""

from __future__ import annotations

import logging
import sys
import threading
import time

logger = logging.getLogger("locust_tpu")


def device_summary() -> dict:
    """What jax reports for this process: ``{"platform", "kind", "count"}``
    (``jax.devices()[0].platform`` / ``.device_kind`` / ``len``).
    Initializes the backend if nothing has yet."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def select_backend(mode: str = "auto") -> str:
    """Resolve AND initialize this process's backend; returns its platform
    (``jax.devices()[0].platform``: "cpu", "tpu", ...).

    Raises RuntimeError when the request cannot be met: ``tpu`` on a
    process whose jax lands anywhere else, ``cpu`` after another backend
    was already initialized.  No mode falls back to a different device.
    """
    if mode not in ("auto", "cpu", "tpu"):
        raise ValueError(f"backend mode must be auto|cpu|tpu, got {mode!r}")
    import jax

    if mode == "cpu":
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform
    if mode != "auto" and platform != mode:
        raise RuntimeError(
            f"backend {mode!r} requested but jax initialized {platform!r} "
            f"({jax.devices()[0].device_kind}); JAX_PLATFORMS="
            f"{jax.config.jax_platforms!r}"
        )
    return platform


class CircuitBreaker:
    """Consecutive-failure circuit breaker for accelerator dispatches.

    A device that initialized can still fail a later dispatch (a chip
    reset, a driver fault).  Per-run retries alone turn that into minutes
    of timeout ladders on EVERY dispatch; the breaker remembers instead
    (Nygard, "Release It!", the canonical stability pattern):

      * ``closed``    — primary dispatches flow; consecutive failures
        count, a success resets the count;
      * ``open``      — ``threshold`` consecutive failures trip it: the
        primary is ineligible (``allow()`` is False) for ``cooldown_s``,
        callers run their fallback (CPU, resumed from the last
        checkpoint — engine.run_checkpointed);
      * ``half_open`` — cooldown over: ``allow()`` returns True exactly
        ONCE (the probe dispatch); success closes the breaker, failure
        re-opens it for another full cooldown.

    Thread-safe; transitions emit ``backend.breaker_*`` instant events so
    a trace timeline shows the trip, the probe and the recovery
    (docs/OBSERVABILITY.md).  ``clock`` is injectable for tests.
    """

    def __init__(self, threshold: int = 3, cooldown_s: float = 30.0,
                 clock=time.monotonic):
        if threshold < 1:
            raise ValueError("breaker threshold must be >= 1")
        if cooldown_s <= 0:
            raise ValueError("breaker cooldown_s must be > 0")
        self.threshold = threshold
        self.cooldown_s = cooldown_s
        self._clock = clock
        self._lock = threading.Lock()
        self._state = "closed"
        self._failures = 0       # consecutive, resets on success
        self._open_until = 0.0
        self._probing = False    # a half-open probe is in flight
        self._trips = 0
        self._successes = 0
        self._failures_total = 0

    def state(self) -> str:
        with self._lock:
            return self._state

    def allow(self) -> bool:
        """May the caller dispatch on the primary backend right now?"""
        event = None
        with self._lock:
            if self._state == "closed":
                return True
            if self._state == "open":
                if self._clock() < self._open_until:
                    return False
                self._state = "half_open"
                self._probing = True
                event = "half_open"
            elif self._probing:
                return False  # one probe at a time; others stay fallback
            else:
                self._probing = True
        if event is not None:
            from locust_tpu import obs

            obs.event("backend.breaker_half_open", cooldown_s=self.cooldown_s)
        return True

    def record_success(self) -> None:
        closed = False
        with self._lock:
            self._successes += 1
            self._failures = 0
            self._probing = False
            if self._state != "closed":
                self._state = "closed"
                closed = True
        if closed:
            from locust_tpu import obs

            obs.event("backend.breaker_close")
            logger.info("backend breaker closed: primary backend restored")

    def record_failure(self) -> None:
        opened = None
        with self._lock:
            self._failures += 1
            self._failures_total += 1
            if self._state == "half_open":
                # The probe failed: a full new cooldown, not a trip.
                self._state = "open"
                self._probing = False
                self._open_until = self._clock() + self.cooldown_s
                opened = "reopen"
            elif self._state == "closed" and self._failures >= self.threshold:
                self._state = "open"
                self._open_until = self._clock() + self.cooldown_s
                self._trips += 1
                opened = "trip"
        if opened is not None:
            from locust_tpu import obs

            obs.event(
                "backend.breaker_open",
                failures=self.threshold if opened == "trip" else 1,
                cooldown_s=self.cooldown_s,
            )
            if opened == "trip":
                obs.metric_inc("backend.breaker_trips")
            logger.warning(
                "backend breaker %s: primary ineligible for %.1fs",
                "tripped" if opened == "trip" else "re-opened",
                self.cooldown_s,
            )

    def stats(self) -> dict:
        with self._lock:
            return {
                "state": self._state,
                "consecutive_failures": self._failures,
                "failures": self._failures_total,
                "successes": self._successes,
                "trips": self._trips,
                "threshold": self.threshold,
                "cooldown_s": self.cooldown_s,
            }


def guarded_dispatch(breaker: CircuitBreaker, fn, **ctx):
    """Run one primary-backend dispatch under the breaker's accounting.

    The ``backend.dispatch`` chaos site fires HERE (docs/FAULTS.md) —
    "error" models the device failing a dispatch after a clean init,
    "delay" a slow one — so the whole trip/failover/half-open ladder is
    drivable from a fault plan.  Any exception out of ``fn`` counts as a
    dispatch failure and re-raises; the caller decides whether to retry
    on the primary or fail over (engine.run_checkpointed reloads the
    last checkpoint either way).
    """
    from locust_tpu.utils import faultplan

    rule = faultplan.fire("backend.dispatch", **ctx)
    if rule is not None:
        if rule.action == "delay":
            time.sleep(rule.delay_s)
        else:
            breaker.record_failure()
            raise faultplan.FaultInjected(
                "[faultplan] injected backend dispatch failure"
            )
    try:
        out = fn()
    except Exception:
        breaker.record_failure()
        raise
    breaker.record_success()
    return out


def cpu_fallback_device():
    """The CPU device in-flight work fails over onto, or None when jax
    has no CPU client (then there is nothing to fail over TO and the
    caller re-raises).  Defensive the same way as the mesh collectives
    flip: a jax refactor degrades to no-failover, never to a crash."""
    try:
        import jax

        return jax.local_devices(backend="cpu")[0]
    except Exception as e:  # pragma: no cover - defensive
        logger.warning("no CPU fallback device available: %s", e)
        return None


def select_backend_cli(mode: str, prog: str = "locust_tpu") -> str | None:
    """CLI-entrypoint wrapper: resolve the backend, print the device line
    (platform, device_kind, count) to stderr before anything runs, and
    turn a refused request into a printed error + None.  The ONE policy
    both the WordCount driver (cli.py) and the workload-ladder
    subcommands (cli_apps.py) use."""
    try:
        backend = select_backend(mode)
    except RuntimeError as e:
        print(f"{prog}: error: {e}", file=sys.stderr)
        return None
    dev = device_summary()
    print(
        f"[locust] backend: {backend} (device_kind={dev['kind']!r} "
        f"count={dev['count']})",
        file=sys.stderr,
    )
    return backend
