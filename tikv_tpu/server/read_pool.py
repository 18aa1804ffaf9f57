"""Read pool — admission control + concurrency cap for read requests.

Reference: src/read_pool.rs (unified yatp read pool with priority and
running-task watermarks, :28-90) and the ServerIsBusy rejection the
scheduler/read path returns under overload.  gRPC already supplies the
worker threads, so the pool's job here is QoS: cap how many reads run
at once (so scans/coprocessor requests cannot starve the write path's
lock acquisition) and reject instead of queueing unboundedly once the
pending watermark trips — the reference's running-threshold behavior.

Priorities: ``high`` (point reads) bypasses the pending watermark the
way the reference's priority scheduling keeps small reads flowing while
big scans queue.

Overload defense on top of the watermark:

- a ``ServerIsBusy`` rejection carries ``retry_after_ms`` derived from
  the queue depth and the EWMA service time, so clients back off by the
  pool's actual drain rate instead of blind exponential jitter;
- deadline-aware shedding: a request whose remaining budget is below
  the EWMA service time is rejected at admission — it would only burn a
  slot producing an answer nobody can use (fail fast, not fail late);
- the service-time EWMA is keyed by COMPILE CLASS (``class_key`` —
  the const-blind plan identity for coprocessor requests, the RPC
  method otherwise; DAGRequest.class_key), falling back to the global
  EWMA for unseen classes: a 10M-row hash-agg and a point-select no
  longer share one figure, so shed decisions and ``retry_after_ms``
  hints reflect the actual cost mix instead of whichever shape ran
  last;
- RU-priced PER-GROUP shedding (resource_control.py): admission also
  compares the request's resource group's RU debt and recent-RU-rate
  EWMA against its configured share — the one-figure-for-everyone
  framing stops here: a background scan group deep in measured RU
  debt sheds (with a ``retry_after_ms`` derived from ITS token
  bucket's refill time, and the ``ServerIsBusy`` response carrying
  the group name) while a latency group's requests keep flowing.
  Work-conserving: an over-budget group is shed only while the pool
  actually has contention, and high-priority groups never shed here.
"""

from __future__ import annotations

import threading
import time

from ..utils.deadline import Deadline, DeadlineExceeded
from ..utils.metrics import (
    DEADLINE_SHED_COUNTER,
    READ_POOL_EMA_GAUGE,
    READ_POOL_PENDING_GAUGE,
    READ_POOL_RUNNING_GAUGE,
)


class ServerIsBusy(Exception):
    def __init__(self, reason: str = "read pool saturated",
                 retry_after_ms: int = 0,
                 resource_group: "str | None" = None):
        super().__init__(reason)
        self.reason = reason
        # queue-depth-derived backoff hint (0 = none); rides the wire
        self.retry_after_ms = retry_after_ms
        # RU-priced per-group shed (resource_control.py): the group
        # that was over budget — rides the wire so a client can tell
        # "my group is throttled" from "the whole store is busy"
        self.resource_group = resource_group


class ReadPool:
    # EWMA smoothing for service time: ~5 samples of memory — fast
    # enough to follow a brownout, slow enough to ignore one outlier
    EMA_ALPHA = 0.2
    # per-compile-class EWMAs retained (LRU); the global EWMA covers
    # evicted/unseen classes
    CLASS_EMA_MAX = 128

    def __init__(self, max_concurrency: int = 8, max_pending: int = 64):
        from collections import OrderedDict
        self._slots = threading.Semaphore(max_concurrency)
        self._mu = threading.Lock()
        self._max_concurrency = max_concurrency
        self._max_pending = max_pending
        self._pending = 0
        self._closed = False
        self._idle = threading.Condition(self._mu)
        self.served = 0
        self.rejected = 0
        self.deadline_shed = 0
        self.rc_shed = 0        # RU-priced per-group rejections
        self.running = 0
        self.running_peak = 0
        self.ema_service_time = 0.0
        # class_key -> (ema_seconds, n_obs); plan-aware shedding input
        self._class_ema: "OrderedDict" = OrderedDict()

    def class_ema(self, class_key) -> float:
        """Service-time EWMA for one compile class; 0.0 when unseen
        (callers fall back to the global figure)."""
        with self._mu:
            got = self._class_ema.get(class_key)
            return got[0] if got is not None else 0.0

    def _ema_for_locked(self, class_key) -> float:
        """The shed-decision figure: the class EWMA once observed, the
        global EWMA otherwise."""
        if class_key is not None:
            got = self._class_ema.get(class_key)
            if got is not None:
                return got[0]
        return self.ema_service_time

    def retry_after_ms(self, class_key=None) -> int:
        """Backoff hint for a busy rejection: how long the CURRENT
        queue takes to drain at the observed service rate (the
        requester's own class rate when known — a cheap point-select
        is not told to wait out a hash-agg's figure)."""
        with self._mu:
            return self._retry_after_ms_locked(class_key)

    def _retry_after_ms_locked(self, class_key=None) -> int:
        waiting = max(0, self._pending - self.running) + 1
        ema = self._ema_for_locked(class_key)
        if ema <= 0:
            return 0
        return max(1, int(1000.0 * ema * waiting / self._max_concurrency))

    def run(self, fn, priority: str = "normal",
            deadline: "Deadline | None" = None, class_key=None,
            resource_group=None):
        """Execute ``fn`` under the pool's concurrency cap.

        Raises ServerIsBusy when the pending watermark is exceeded
        (normal priority only — high-priority point reads always admit)
        and DeadlineExceeded / ServerIsBusy when ``deadline`` is already
        expired / below the EWMA service time (deadline-aware shedding;
        applies to every priority — an unservable point read is still
        unservable).  ``class_key`` selects the per-compile-class EWMA
        for the shed comparison and the retry hint; the observed
        service time updates both that class and the global figure.
        ``resource_group`` feeds the RU-priced per-group admission
        gate (resource_control.py): an over-budget group sheds under
        pool contention with a retry hint derived from its own token
        bucket's refill time.
        """
        if deadline is not None:
            deadline.check("read_pool")      # expired: typed shed
            rem = deadline.remaining()
            with self._mu:
                ema = self._ema_for_locked(class_key)
            if ema > 0 and rem < ema:
                with self._mu:
                    self.deadline_shed += 1
                    self.rejected += 1
                DEADLINE_SHED_COUNTER.labels("read_pool_predict").inc()
                raise ServerIsBusy(
                    f"remaining budget {rem * 1e3:.1f}ms < ema service "
                    f"time {ema * 1e3:.1f}ms",
                    retry_after_ms=self.retry_after_ms(class_key))
        # RU-priced per-group admission (enforcement site 3, module
        # doc), AFTER the deadline gate: an already-expired request
        # must get the typed deadline shed, never a retryable busy
        # its group's refill time would make it sleep on.  Before the
        # watermark: an over-budget group is shed before it can
        # occupy pending-queue headroom, and the copr::rc_throttle
        # failpoint fires even for requests the watermark would
        # admit.  Gated on one attribute read + a non-firing
        # failpoint peek — the shipped default (controller off, site
        # cold) pays no extra lock round trip.
        from ..resource_control import GLOBAL_CONTROLLER as _rc
        from ..utils.failpoint import is_armed as _fp_armed
        if _rc.enabled or _fp_armed("copr::rc_throttle"):
            with self._mu:
                busy = (self._pending - self.running) > 0 or \
                    self.running >= self._max_concurrency
            ok, rc_hint, rc_reason = _rc.admit(resource_group,
                                               pool_busy=busy)
            if not ok:
                with self._mu:
                    self.rc_shed += 1
                    self.rejected += 1
                raise ServerIsBusy(rc_reason, retry_after_ms=rc_hint,
                                   resource_group=resource_group
                                   or "default")
        with self._mu:
            if self._closed:
                raise ServerIsBusy("read pool shut down")
            if priority != "high" and self._pending >= self._max_pending:
                self.rejected += 1
                raise ServerIsBusy(
                    f"{self._pending} reads pending (max "
                    f"{self._max_pending})",
                    retry_after_ms=self._retry_after_ms_locked(class_key))
            self._pending += 1
            self._publish_gauges()
        try:
            from ..utils import tracker
            t_wait = time.perf_counter_ns()
            with self._slots:
                tracker.add_wait(time.perf_counter_ns() - t_wait)
                with self._mu:
                    self.served += 1
                    self.running += 1
                    # running-task watermark (read_pool.rs
                    # running_threads tracking feeding busy decisions)
                    self.running_peak = max(self.running_peak,
                                            self.running)
                    self._publish_gauges()
                t0 = time.perf_counter()
                try:
                    return fn()
                finally:
                    dt = time.perf_counter() - t0
                    # RU metering: host service wall under this slot,
                    # charged to the request's tag/region (the context
                    # the service stamped on the trace at admission —
                    # the same class_key identity that keys the EWMA
                    # below keys the enforcement PR's per-class cost
                    # model).  Deferred device fetches are NOT in this
                    # figure: the slot covers only the dispatch, and
                    # the device axes charge at their own sites.
                    # This prices SLOT OCCUPANCY, deliberately: a solo
                    # device request's dispatch enqueue runs under the
                    # slot and is billed here ON TOP of its
                    # device::launch charge (it consumes both scarce
                    # resources at once), while a coalesced member's
                    # dispatch runs on the coalescer thread and holds
                    # no slot — batching genuinely costs the host less
                    # and the RU figures say so.
                    from ..resource_metering import GLOBAL_RECORDER
                    GLOBAL_RECORDER.charge("read_pool::host",
                                           host_s=dt)
                    with self._mu:
                        self.running -= 1
                        self.ema_service_time = dt if \
                            self.ema_service_time == 0.0 else \
                            (self.EMA_ALPHA * dt + (1 - self.EMA_ALPHA)
                             * self.ema_service_time)
                        if class_key is not None:
                            got = self._class_ema.pop(class_key, None)
                            if got is None:
                                self._class_ema[class_key] = (dt, 1)
                            else:
                                ema_c, n_c = got
                                self._class_ema[class_key] = (
                                    self.EMA_ALPHA * dt +
                                    (1 - self.EMA_ALPHA) * ema_c,
                                    n_c + 1)
                            while len(self._class_ema) > \
                                    self.CLASS_EMA_MAX:
                                self._class_ema.popitem(last=False)
                        READ_POOL_EMA_GAUGE.set(self.ema_service_time)
                        self._publish_gauges()
        finally:
            with self._mu:
                self._pending -= 1
                self._publish_gauges()
                if self._pending == 0:
                    self._idle.notify_all()

    def shutdown(self, timeout: float = 5.0) -> bool:
        """Stop admitting and wait for in-flight reads to drain (node
        stop(): restarted-in-process nodes must not leave reads running
        against a torn-down storage stack).  → True when idle."""
        deadline = time.monotonic() + timeout
        with self._mu:
            self._closed = True
            while self._pending > 0:
                rem = deadline - time.monotonic()
                if rem <= 0:
                    return False
                self._idle.wait(rem)
        return True

    def _publish_gauges(self) -> None:
        """Caller holds the lock.  'pending' exposes tasks WAITING for
        a slot (admitted minus running) so saturation alerts don't fire
        on merely-executing reads."""
        READ_POOL_RUNNING_GAUGE.set(self.running)
        READ_POOL_PENDING_GAUGE.set(max(0, self._pending - self.running))

    def stats(self) -> dict:
        with self._mu:
            return {"running": self.running,
                    "pending": max(0, self._pending - self.running),
                    "served": self.served, "rejected": self.rejected,
                    "deadline_shed": self.deadline_shed,
                    "rc_shed": self.rc_shed,
                    "ema_service_time_ms":
                        round(self.ema_service_time * 1e3, 3),
                    "ema_classes": len(self._class_ema)}


class CompletionPool:
    """Small worker pool that overlaps deferred device completions.

    The async coprocessor path dispatches a kernel under a ReadPool
    slot (cheap — an enqueue), releases the slot, and hands the
    blocking D2H fetch + host finalize here.  The workers spend their
    time parked inside the device runtime's transfer wait (GIL
    released), so ``workers`` concurrent fetches overlap on the wire
    instead of serializing one sync round trip each (~1-2 ms
    co-located — copr/endpoint.py), and heavy coprocessor traffic
    never holds read-pool slots hostage while waiting on the transport.

    Priorities mirror ReadPool's two-level scheme: ``high`` (KB-sized
    aggregate states) drains before ``normal`` (bulk TopN/selection
    candidate readbacks), so a cheap agg answer is never queued behind
    a multi-MB transfer.  Results ride stdlib
    ``concurrent.futures.Future``s (only the priority queue is custom).

    ``shutdown()`` drains queued tasks, retires the workers, and JOINS
    them — owners that come and go (server nodes restarted in-process,
    per-test endpoints) must call it or leak ``workers`` parked threads
    each.
    """

    def __init__(self, workers: int = 4):
        self._workers = max(1, workers)
        self._mu = threading.Lock()
        self._cv = threading.Condition(self._mu)
        self._high: list = []
        self._normal: list = []
        self._threads: list = []
        self._started = False
        self._shutdown = False
        self.completed = 0

    def submit(self, fn, priority: str = "normal"):
        import concurrent.futures as cf

        from ..utils import tracker
        # queue-wait attribution: the span tree must show time a
        # deferred fetch spent WAITING for a completion worker apart
        # from the D2H wait itself — under completion-pool saturation
        # that queue is exactly where warm-path latency hides
        cur = tracker.current()
        if cur is not None:
            t_enq = time.perf_counter_ns()
            inner = fn

            def fn():
                tok = tracker.adopt(cur)
                try:
                    tracker.add_phase(
                        "completion_queue_wait",
                        time.perf_counter_ns() - t_enq)
                finally:
                    tracker.uninstall(tok)
                return inner()
        fut: "cf.Future" = cf.Future()
        with self._mu:
            if self._shutdown:
                fut.set_exception(RuntimeError("completion pool is shut "
                                               "down"))
                return fut
            (self._high if priority == "high" else
             self._normal).append((fn, fut))
            if not self._started:
                self._started = True
                for i in range(self._workers):
                    t = threading.Thread(target=self._worker, daemon=True,
                                         name=f"copr-completion-{i}")
                    self._threads.append(t)
                    t.start()
            self._cv.notify()
        return fut

    def shutdown(self, timeout: float = 5.0) -> None:
        """Stop accepting work; workers finish the queue, then exit —
        joined here so a stop() caller observes zero leaked threads."""
        with self._mu:
            self._shutdown = True
            self._cv.notify_all()
            threads = list(self._threads)
        for t in threads:
            t.join(timeout)

    def _worker(self) -> None:
        while True:
            with self._mu:
                while not self._high and not self._normal:
                    if self._shutdown:
                        return
                    self._cv.wait()
                fn, fut = (self._high or self._normal).pop(0)
            try:
                fut.set_result(fn())
            except BaseException as e:  # noqa: BLE001 — ride the future
                fut.set_exception(e)
            with self._mu:
                self.completed += 1
