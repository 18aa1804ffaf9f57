"""Cross-request device batching — coalescing dispatcher + cost router.

The heavy-traffic serving subsystem (ROADMAP "Heavy-traffic serving"):
thousands of concurrent small coprocessor queries each paid their own
device dispatch, their own D2H sync, and their own trip through the
read pool, though the hardware amortizes those fixed costs across
in-flight work.  The accelerator's economics are BATCH economics
(Jouppi et al., PAPERS.md): a launch plus a transfer sync is a fixed
tax, so the unit of dispatch must be a *group* of requests, exactly as
MonetDB/X100 made the unit of interpretation a vector of tuples
instead of one.

Two pieces:

:class:`RequestCoalescer` — concurrent requests that target a
co-resident HBM feed and share a compile class (the const-blind
``shape_key`` from the hoisted-parameter selection kernels, or a
byte-identical plan) are grouped into ONE stacked device dispatch with
a shared D2H, under a bounded, deadline-aware collection window, and
closed groups over DIFFERENT feeds of one compile class share a launch
as its lanes:

- a group closes on SIZE (``max_group`` members), WINDOW expiry
  (``window_ms``), or tightest-deadline PRESSURE — a member is never
  held past the point where waiting would eat its remaining budget
  (the zero-late-acks contract from the deadline-propagation work);
- IDLE BYPASS: a request arriving with nothing parked and nothing in
  flight dispatches immediately (occupancy 1) — a serial workload pays
  zero added latency, and the window only engages once a second
  request arrives while the first is still in flight, which is exactly
  when batching has something to amortize (the dynamic-batching rule
  inference servers use);
- ``("stack", ...)`` groups stack each member's hoisted predicate
  constants as a leading axis of the traced scalar params
  (device/selection.build_batched_mask_kernel) — differing thresholds,
  one launch; ``("share", ...)`` groups (identical plans — the
  dashboard thundering herd) share one solo dispatch and one fetch;
- results resolve through the endpoint's CompletionPool as per-request
  slices: ONE fetch, N resolutions, with each member's host gather
  running on its own completion worker;
- a group is one co-resident feed, and a LAUNCH may hold several:
  when the dispatcher takes a closed ``share`` group it takes with it
  every other group that WAITS, closed behind it in ``_ready`` or still
  collecting in ``_open``, whose launch the runner can fuse with it
  (``DeviceRunner.launch_class``: same runner or slice, same plan, same
  kernel compile class; a hash aggregation on the Pallas body).  They
  leave as the LANES of ONE launch (``DeviceRunner.handle_lanes``: one
  staging under one hold of the dispatch lock, one program that runs
  the kernel once a lane over that lane's feed alone, one fetch), each
  lane still its own answer for its own snapshot; a group of a key
  already among them joins that lane.  What the runner resolved to tell
  a group's class it hands over with it (``DeviceRunner.launch_ticket``
  → ``_Group.ticket``: the plan, the line's memo, the prepared record,
  the generation), and the group's lane is staged from that ticket: the
  ask is a prepared hit's only look-up, inside the hold
  (``group_open``).  Nothing is held back for it and
  nothing waits longer: a closed group waits for nothing but the
  dispatcher thread, and an open one is closed early (``lanes``), the
  launch its window was waiting to amortize being this one; so there
  is no second window, no knob, and a lone request never waits;
  ``stack`` groups, other plan kinds, a mesh, a bucket-tile request, a
  class mismatch and a kernel whose lane programs are still building
  take the one-group path.  ``stats()`` (``/health`` ``coalescer``)
  counts it: ``lanes_hist`` (launches by lane count;
  ``multi_lane_launches``, ``lanes_sum``), ``groups_merged`` (waiting
  groups folded into another's launch as lanes of their own),
  ``same_lane_merges`` (folded into a lane of their own key),
  ``closes["lanes"]`` (of both, those taken while still open),
  ``lane_class_mismatch`` (left behind: another class),
  ``launch_classes`` (distinct classes such launches left under),
  ``unbuilt_fallbacks`` (the runner's: asks that found a kernel's lane
  programs still being built, off this thread: the groups then leave
  one by one, and no staging waits for a compile);
- the group pins its arena lines once (generation-guarded pin tokens,
  device/supervisor.py) for the shared dispatch; each lane of a merged
  launch pins its own line, released once when that lane resolves;
- a failed group NEVER fails its members: a batched-launch failure
  (incl. the ``copr::coalesce_dispatch`` failpoint, and a lane whose
  staging or launch failed) retries every member as a solo dispatch,
  and a fetch-side fault degrades each
  member to the host pipeline through the endpoint's existing
  per-request contract.  That contract extends across CHIP DEATH
  (device/supervisor.py failure domains): a group whose slice dies
  between dispatch and fetch rescues PER MEMBER onto a healthy slice
  (the placer re-pins the anchor; _BatchedSelectionGroup.member_result
  catches the shared-fetch fault), the solo retries re-route through
  the placer — which now excludes the quarantined slice — and the
  group's arena pin still releases exactly once inside the memoized
  shared fetch, dead chip or not.

:class:`CostRouter` — generalizes the read pool's EWMA shedding into a
per-request, Jouppi-style cost decision over four outcomes:

- ``device_batched``: launch overhead amortized over the expected
  group occupancy (EWMA of recent group sizes) + the member's D2H
  bytes; the expected collection wait (the open group's remaining
  window, half a window when none is open) counts against the
  request's DEADLINE feasibility but never against the backend
  choice — wait is latency the member sits out, not a resource
  either backend consumes, and charging it as cost would mean any
  window longer than the host cost forces all traffic host and the
  occupancy that justifies the window could never form;
- ``device_solo``: full launch overhead + D2H — taken when the plan
  cannot share a dispatch or the deadline cannot afford a window;
- ``host``: the modeled host-pipeline cost undercuts both device
  options.  The host model is CALIBRATED from the endpoint's
  ``device_row_threshold`` — the operator-tuned, transport-measured
  break-even (endpoint.py rationale) — so at zero load the router
  never re-litigates the threshold's verdict; device costs additionally
  carry the CURRENT backlog (members parked + in flight), so under a
  device pile-up the marginal request overflows to the host CPU
  instead of queueing — the slow-store-drain idea applied to the
  accelerator itself;
- ``shed``: the remaining deadline cannot fit even the cheapest
  option — reject NOW with a ``retry_after_ms`` hint instead of
  burning device time on an answer nobody can use (the read-pool
  ``remaining < ema`` rule, upgraded from one global EWMA to a
  modeled per-request cost).

Launch overhead is MEASURED (EWMA over observed dispatch walls, seeded
conservatively); D2H bytes come from the runner's per-plan selectivity
EWMAs for selections (mask payload = n/8) and a small-constant agg
readback otherwise.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Optional

from ..utils.failpoint import fail_point
from ..utils.metrics import (
    COPR_BATCH_OCCUPANCY,
    COPR_COALESCE_CLOSE_COUNTER,
    COPR_ROUTER_COUNTER,
)

DEVICE_BATCHED = "device_batched"
DEVICE_SOLO = "device_solo"
HOST = "host"
SHED = "shed"


class CostRouter:
    """Per-request admission decision from a measured cost model."""

    # EWMA seeds/rates.  The launch figure is the dispatch+sync fixed
    # cost on co-located chips (~1-2ms); the EWMA converges on the
    # measured figure after the first groups.
    LAUNCH_SEED_S = 1.5e-3
    LAUNCH_ALPHA = 0.2
    OCC_ALPHA = 0.3
    # modeled D2H link rate (static seed — the measured quantities are
    # the launch overhead and the per-plan selectivity EWMAs; this only
    # scales byte counts into comparable seconds)
    D2H_BYTES_PER_S = 8e9
    AGG_D2H_BYTES = 1 << 16
    # host-cost calibration anchor: at n == device_row_threshold the
    # host pipeline and a solo dispatch break even BY MEASUREMENT
    # (that is what the threshold means — endpoint.py rationale), and
    # a warm solo dispatch's cost IS the launch EWMA — so host cost is
    # modeled as (n / threshold) × the LIVE launch figure.  Anchoring
    # on the measured EWMA instead of a frozen seed keeps the two
    # sides of the comparison consistent on any transport (a slower
    # launch scales the host model with it); deployments that retune
    # the threshold retune the host model too.
    DEFAULT_ROW_THRESHOLD = 131072
    # shed margin: remaining budget must cover the cheapest option with
    # this headroom, else the request is rejected with a hint
    SHED_MARGIN = 2.0
    # the endpoint's row threshold already vetted the device for this
    # request (transport-bound crossover, endpoint.py rationale); the
    # router diverts it back to host only on a CLEAR modeled win, so
    # model noise near the crossover cannot starve the batch pipeline
    # of the occupancy that makes it profitable
    HOST_BIAS = 2.0

    def __init__(self, coalescer: "RequestCoalescer", runner):
        self._coalescer = coalescer
        self._runner = runner
        self._mu = threading.Lock()
        self.launch_ewma = self.LAUNCH_SEED_S
        self.occupancy_ewma = 1.0
        self.decisions: dict[str, int] = {}

    # -- measurement feedback --

    def note_launch(self, wall_s: float, occupancy: int) -> None:
        """One group dispatched: fold the observed dispatch wall and
        the group size into the model.  The wall covers enqueue + any
        warm-path kernel lookup — the fixed cost the next request
        would pay solo."""
        with self._mu:
            self.launch_ewma = (self.LAUNCH_ALPHA * wall_s +
                                (1 - self.LAUNCH_ALPHA) * self.launch_ewma)
            self.occupancy_ewma = (self.OCC_ALPHA * occupancy +
                                   (1 - self.OCC_ALPHA) *
                                   self.occupancy_ewma)

    # -- the decision --

    def _d2h_bytes(self, dag, n: Optional[int]) -> float:
        """Modeled member D2H payload: packed mask (n/8) for
        selections — the stacked route's per-member payload — scaled
        down by the plan's observed-selectivity EWMA when the index/
        compact routes would undercut it; small constant for
        aggregations (KB-class packed states)."""
        runner = self._runner
        try:
            plan = runner._analyze(dag)
        except Exception:   # noqa: BLE001 — unanalyzable → agg-class
            plan = None
        if plan is None or plan.kind != "scan_sel" or not n:
            return float(self.AGG_D2H_BYTES)
        mask_bytes = n / 8.0
        try:
            pred = runner._sel_predict(runner._sel_keys(dag, plan))
        except Exception:   # noqa: BLE001
            pred = None
        if pred is not None:
            from ..device import selection as selmod
            route = selmod.choose_route(n, pred * n, False)
            return float(min(mask_bytes, selmod.modeled_d2h_bytes(
                route, n, int(pred * n))))
        return mask_bytes

    def _host_s_per_row(self, launch: float) -> float:
        ep = getattr(self._coalescer, "_endpoint", None)
        thr = getattr(ep, "_device_row_threshold", 0) or \
            self.DEFAULT_ROW_THRESHOLD
        return launch / max(1, thr)

    def route(self, dag, storage) -> tuple:
        """→ ``(decision, batch_key, retry_after_ms)``.

        ``batch_key`` is non-None only for ``device_batched``;
        ``retry_after_ms`` only for ``shed``.  Batching is the DEFAULT
        for batchable device requests with deadline slack — collection
        windows are how occupancy (and thus amortization) materializes,
        and the idle bypass keeps the default free for serial traffic —
        while host/shed trigger on the modeled comparison."""
        from ..utils import deadline as dl_mod
        coal = self._coalescer
        est = getattr(storage, "estimated_rows", None)
        n = est() if callable(est) else None
        key = self._runner.batch_class(dag, storage) \
            if coal.enabled else None
        with self._mu:
            launch = self.launch_ewma
            occ = max(1.0, self.occupancy_ewma)
        busy = coal.busy()
        d2h_s = self._d2h_bytes(dag, n) / self.D2H_BYTES_PER_S
        # RESOURCE costs — what each option consumes.  Device
        # dispatches serialize (the runner's dispatch lock): each
        # backlogged member is ~one launch ahead of this request.
        # Groups absorb backlog max_group at a time, so the batched
        # queue term divides by the group size.  The collection-window
        # wait is deliberately NOT in these figures (module doc): it
        # is latency, entering only the deadline-feasibility terms.
        cost_solo = launch * (1.0 + busy) + d2h_s
        cost_batched = (launch * (1.0 + busy / coal.max_group) / occ +
                        d2h_s) if key is not None else float("inf")
        cost_host = n * self._host_s_per_row(launch) if n \
            else float("inf")
        wait = coal.expected_wait_s(key) if key is not None else 0.0
        best = min(cost_solo, cost_batched + wait, cost_host)
        dl = dl_mod.current()
        rem = dl.remaining() if dl is not None else None
        if rem is not None and rem < best * self.SHED_MARGIN:
            hint = max(1, int(best * 1e3))
            return self._note(SHED), None, hint
        if cost_host * self.HOST_BIAS < min(cost_solo, cost_batched):
            return self._note(HOST), None, 0
        if key is not None and (
                rem is None or
                rem > 2.0 * self.SHED_MARGIN * cost_solo):
            # batch even when the budget cannot afford the FULL window:
            # the coalescer tightens the group's close time to the
            # tightest member's remaining budget (deadline-pressure
            # close), so joining costs at most the slack the member
            # actually has — only a budget too tight for the
            # post-dispatch work itself forces a solo dispatch
            return self._note(DEVICE_BATCHED), key, 0
        return self._note(DEVICE_SOLO), None, 0

    def route_fast(self, n, d2h_bytes: float, key) -> tuple:
        """``route()`` for the compiled fast path (server/fastpath.py):
        the PER-PLAN modeled figures — estimated rows ``n`` and the
        D2H payload — were computed on the class's slow-path learn
        request and ride the class entry, so a hit pays no plan
        re-analysis; every LIVE figure (launch EWMA, occupancy,
        backlog, the open window, the deadline) is read exactly as
        ``route()`` reads it, so shed / host-overflow / batching
        decisions keep tracking the measured load.  The learned D2H
        figure can lag a drifting selectivity EWMA by up to one
        re-learn; the drift only shifts the host-vs-device comparison,
        never correctness, and any invalidation re-anchors it."""
        from ..utils import deadline as dl_mod
        coal = self._coalescer
        with self._mu:
            launch = self.launch_ewma
            occ = max(1.0, self.occupancy_ewma)
        busy = coal.busy()
        d2h_s = d2h_bytes / self.D2H_BYTES_PER_S
        cost_solo = launch * (1.0 + busy) + d2h_s
        cost_batched = (launch * (1.0 + busy / coal.max_group) / occ +
                        d2h_s) if key is not None else float("inf")
        cost_host = n * self._host_s_per_row(launch) if n \
            else float("inf")
        wait = coal.expected_wait_s(key) if key is not None else 0.0
        best = min(cost_solo, cost_batched + wait, cost_host)
        dl = dl_mod.current()
        rem = dl.remaining() if dl is not None else None
        if rem is not None and rem < best * self.SHED_MARGIN:
            hint = max(1, int(best * 1e3))
            return self._note(SHED), None, hint
        if cost_host * self.HOST_BIAS < min(cost_solo, cost_batched):
            return self._note(HOST), None, 0
        if key is not None and (
                rem is None or
                rem > 2.0 * self.SHED_MARGIN * cost_solo):
            return self._note(DEVICE_BATCHED), key, 0
        return self._note(DEVICE_SOLO), None, 0

    def _note(self, decision: str) -> str:
        COPR_ROUTER_COUNTER.labels(decision).inc()
        from ..utils import tracker
        tracker.label("router", decision)
        with self._mu:
            self.decisions[decision] = self.decisions.get(decision, 0) + 1
        return decision

    def stats(self) -> dict:
        with self._mu:
            return {
                "launch_ewma_ms": round(self.launch_ewma * 1e3, 3),
                "occupancy_ewma": round(self.occupancy_ewma, 3),
                "decisions": dict(self.decisions),
            }


class _Member:
    """One request parked in a collection window."""

    __slots__ = ("dag", "storage", "future", "tracker", "tag",
                 "deadline_at", "t_submit_ns", "rc_defers", "t_closed_ns")

    def __init__(self, dag, storage, future, tracker, tag, deadline_at):
        self.dag = dag
        self.storage = storage
        self.future = future
        self.tracker = tracker
        self.tag = tag
        self.deadline_at = deadline_at
        self.t_submit_ns = time.perf_counter_ns()
        # collection windows this member was DWFQ-deferred past
        # (resource_control.select_stacked bounds it at MAX_DEFERS)
        self.rc_defers = 0
        # when its group was queued for the dispatcher (_close_locked;
        # 0: dispatched inline at shutdown, never queued).  On the
        # member, not only on the group: groups merged into one launch
        # keep each its own instant
        self.t_closed_ns = 0


_UNASKED = object()


class _Group:
    __slots__ = ("key", "members", "close_at", "window_close_at",
                 "closed", "t_closed_ns", "klass", "ticket")

    def __init__(self, key, close_at: float):
        self.key = key
        self.members: list[_Member] = []
        self.close_at = close_at            # only ever tightens
        self.window_close_at = close_at     # the untightened window
        self.closed = False
        # when _close_locked queued it for the dispatcher (0: a
        # shutdown-time group dispatched inline, never queued)
        self.t_closed_ns = 0
        # the runner's launch class of the group's launch, asked once
        # (_launch_class; None: it leaves alone), and what the runner
        # resolved to find it, the group's TICKET: handed back with the
        # lane, so that a prepared hit is staged without a second
        # look-up (DeviceRunner.launch_ticket; None: no class, or a
        # runner that gives none)
        self.klass = _UNASKED
        self.ticket = None


class RequestCoalescer:
    """The coalescing dispatcher (module doc).  Owned by the endpoint;
    one per node.  Lazy dispatcher thread — endpoints that never see a
    device-batched request never start it."""

    # post-dispatch latency reserve subtracted from a member's deadline
    # when tightening the group's close time: a request must leave the
    # window with enough budget for its dispatch + fetch + gather.
    # Deliberately GENEROUS (and scaled by the measured launch EWMA):
    # over-reserving only closes a group a little early — losing a
    # member or two of occupancy — while under-reserving serves an
    # answer past its deadline, which the zero-late-acks contract
    # forbids outright.
    RESERVE_FLOOR_S = 50e-3
    # a member may spend at most this fraction of its REMAINING budget
    # parked in a collection window; the rest stays for the dispatch +
    # fetch + gather (whose first-group cost includes the stacked
    # kernel's compile — far above the steady-state launch EWMA, so an
    # EWMA-scaled reserve alone cannot cover it)
    WAIT_FRACTION = 0.25

    def __init__(self, runner, window_ms: float = 2.0,
                 max_group: int = 16, pipeline: bool = True):
        self._runner = runner
        self.window_s = max(0.0, window_ms) / 1e3
        self.max_group = max(1, int(max_group))
        self.enabled = True
        self.router = CostRouter(self, runner)
        self._endpoint = None
        self._mu = threading.Lock()
        self._cv = threading.Condition(self._mu)
        self._open: dict = {}
        self._ready: deque = deque()
        self._thread: Optional[threading.Thread] = None
        self._dispatcher: Optional[threading.Thread] = None
        # persistent back-to-back dispatcher: collection (window
        # management, on the collector thread) overlaps launch staging
        # (feed/kernel lookup + enqueue, on the dispatcher thread), so
        # while one group's launch is being staged or is in flight the
        # next group is already collecting — and the moment the device
        # runs DRY (nothing staged, nothing unresolved) the dispatcher
        # feeds it the oldest open group early ("pipeline" close)
        # instead of letting it idle out a collection window.  Closing
        # early is always deadline-safe; it trades a little occupancy
        # for never leaving the device idle while members wait — the
        # X100 hyper-pipelining rule applied to the dispatch stream.
        # Gated with idle_bypass: deterministic-window tests switch
        # both off.
        self.pipeline = bool(pipeline)
        self._shutdown = False
        # members closed-for-dispatch whose futures have not resolved;
        # drives the idle-bypass busy signal
        self._inflight = 0
        # False: always collect for the window (deterministic tests)
        self.idle_bypass = True
        # counters (under _mu)
        self.groups_dispatched = 0
        self.requests_coalesced = 0
        self.solo_degrade = 0
        self.occupancy_sum = 0
        self.max_observed_occupancy = 0
        self.closes: dict[str, int] = {}
        # resource-control deferrals: members a closed group's DWFQ
        # selection re-parked into the key's next window (never
        # dropped — they dispatch later, solo, or at shutdown inline)
        self.rc_deferrals = 0
        # plan-IR share class (endpoint.handle_plan): in-flight
        # executions keyed by (plan identity, snapshot generations);
        # a byte-identical concurrent join plan JOINS the running
        # execution instead of dispatching its own — the ("share", ...)
        # thundering-herd semantics applied to the plan path, without
        # a collection window (the first arrival never waits)
        self._shared: dict = {}
        self.plan_share_hits = 0
        self.plan_share_groups = 0
        # merged launches (_take_fusable, module doc): launches by lane
        # count; ready groups folded into another group's launch, as
        # lanes of their own (groups_merged) or into a lane of the same
        # key (same_lane_merges); ready groups left behind because
        # their launch class differed (lane_class_mismatch).  stats()
        # adds the runner's unbuilt_fallbacks: groups that left alone
        # because their kernel's lane programs were still building
        self.lanes_hist: dict[int, int] = {}
        self.groups_merged = 0
        self.same_lane_merges = 0
        self.lane_class_mismatch = 0
        # the distinct launch classes a launch left under while other
        # groups waited (the class is asked only then): one a cell of
        # one plan, three where Q1, Q6 and Q15 meet on one store.  The
        # dispatcher alone adds; bounded by the kernel cache's classes
        self._launch_classes: set = set()

    # ------------------------------------------------------------ wiring

    def bind(self, endpoint) -> None:
        """Attach the owning endpoint (completion pool provider)."""
        self._endpoint = endpoint

    def set_enabled(self, on: bool) -> None:
        """Router gate: disabled → every device request routes solo
        (the bench's forced per-request phase; online-config toggle via
        window=0 recreates, this flips in place)."""
        self.enabled = bool(on)

    def configure(self, window_ms: Optional[float] = None,
                  max_group: Optional[int] = None) -> None:
        with self._mu:
            if window_ms is not None:
                self.window_s = max(0.0, float(window_ms)) / 1e3
                self.enabled = window_ms > 0
            if max_group is not None:
                self.max_group = max(1, int(max_group))

    def route(self, dag, storage) -> tuple:
        return self.router.route(dag, storage)

    def busy(self) -> int:
        """Device backlog proxy: members parked in open windows plus
        dispatched-but-unresolved members (the router's queue term)."""
        with self._mu:
            return self._inflight + sum(len(g.members)
                                        for g in self._open.values())

    def expected_wait_s(self, key) -> float:
        """Modeled collection wait for a request joining ``key``'s
        group NOW: the open group's remaining window when one exists
        (a joiner inherits its close time), else half a window (the
        expectation when this request opens the group and a size/
        pressure close may beat the timer)."""
        with self._mu:
            g = self._open.get(key)
            if g is not None and not g.closed:
                return max(0.0, g.close_at - time.monotonic())
        return self.window_s / 2.0

    # ------------------------------------------------------------ submit

    def submit(self, key, dag, storage, tag=None):
        """Park one request into its group; → a Future resolving to the
        member's SelectResult.  Called from handle_async under the
        read-pool slot — nothing here blocks beyond the group lock."""
        import concurrent.futures as cf

        from ..utils import deadline as dl_mod
        from ..utils import tracker
        fut: "cf.Future" = cf.Future()
        dl = dl_mod.current()
        deadline_at = (time.monotonic() + dl.remaining()) \
            if dl is not None else None
        member = _Member(dag, storage, fut, tracker.current(), tag,
                         deadline_at)
        now = time.monotonic()
        reserve = max(self.RESERVE_FLOOR_S,
                      8.0 * self.router.launch_ewma)
        inline = False      # dispatch on THIS thread (shutdown only)
        with self._cv:
            if self._shutdown:
                # the endpoint is tearing down but a straggler arrived:
                # serve it as an immediate singleton (no window).  The
                # inline flag — not a re-read of _shutdown below —
                # marks it for dispatch on this thread: a group closed
                # on the NORMAL path is already queued for the
                # dispatcher loop, and a close() racing in between the
                # lock release and the check must not dispatch it twice
                g = _Group(key, now)
                g.members.append(member)
                g.closed = True
                self._inflight += 1     # _on_member_done undoes it
                self.closes["shutdown"] = \
                    self.closes.get("shutdown", 0) + 1
                COPR_COALESCE_CLOSE_COUNTER.labels("shutdown").inc()
                inline = True
            else:
                self._ensure_thread()
                g = self._open.get(key)
                if g is None or g.closed:
                    g = _Group(key, now + self.window_s)
                    self._open[key] = g
                g.members.append(member)
                if member.deadline_at is not None:
                    rem = member.deadline_at - now
                    g.close_at = min(g.close_at,
                                     member.deadline_at - reserve,
                                     now + self.WAIT_FRACTION * rem)
                parked = sum(len(og.members)
                             for og in self._open.values()) - 1
                reason = None
                if len(g.members) >= self.max_group:
                    reason = "size"
                elif fail_point("copr::coalesce_window") is not None:
                    reason = "failpoint"
                elif g.close_at <= now:
                    reason = "deadline"
                elif self.idle_bypass and self._inflight == 0 and \
                        parked == 0:
                    # nothing to amortize against: dispatch NOW —
                    # serial workloads never pay the window
                    reason = "idle"
                if reason is not None:
                    self._close_locked(g, reason)
                # notify_all, not notify: TWO threads wait on this
                # condition (collector + dispatcher) and a lone notify
                # may wake only the dispatcher — which has nothing to
                # stage — while the collector sleeps out a stale
                # timeout past a freshly TIGHTENED close_at (a 2s-
                # budget member joining a 10s window must wake the
                # collector, or it acks late)
                self._cv.notify_all()
        member.future.add_done_callback(self._on_member_done)
        if inline:
            self._dispatch(g)
        return fut

    # ------------------------------------------------------ plan share

    def submit_shared(self, key, fn):
        """Join plans' batch class: run ``fn`` once per concurrent
        ``key`` — late arrivals park on the leader's future and share
        its result (a failed leader fails every sharer; each caller's
        own retry/degrade policy then applies).  The leader executes on
        ITS OWN thread — no window, no added latency for serial
        traffic."""
        import concurrent.futures as cf
        with self._mu:
            fut = self._shared.get(key)
            if fut is not None:
                self.plan_share_hits += 1
                leader = False
            else:
                fut = self._shared[key] = cf.Future()
                self.plan_share_groups += 1
                leader = True
        if not leader:
            return fut.result()
        try:
            result = fn()
        except BaseException as e:
            fut.set_exception(e)
            raise
        else:
            fut.set_result(result)
            return result
        finally:
            with self._mu:
                self._shared.pop(key, None)

    # ------------------------------------------------------- group close

    def _close_locked(self, g: _Group, reason: str) -> None:
        if g.closed:
            return
        g.closed = True
        g.t_closed_ns = time.perf_counter_ns()
        for m in g.members:
            m.t_closed_ns = g.t_closed_ns
        if self._open.get(g.key) is g:
            del self._open[g.key]
        self._ready.append(g)
        self._inflight += len(g.members)
        self.closes[reason] = self.closes.get(reason, 0) + 1
        COPR_COALESCE_CLOSE_COUNTER.labels(reason).inc()
        self._cv.notify_all()   # wake the dispatcher for the new group

    def _on_member_done(self, _fut) -> None:
        with self._mu:
            self._inflight = max(0, self._inflight - 1)
            if self._inflight == 0:
                # the device just ran dry: the dispatcher may feed it
                # an open group early (pipeline close)
                self._cv.notify_all()

    def _ensure_thread(self) -> None:
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._collect_loop, daemon=True,
                name="copr-coalescer")
            self._thread.start()
        if self._dispatcher is None:
            self._dispatcher = threading.Thread(
                target=self._dispatch_loop, daemon=True,
                name="copr-dispatcher")
            self._dispatcher.start()

    def _collect_loop(self) -> None:
        """Window management only: close groups whose time is up; the
        dispatcher thread stages their launches — collection of group
        N+1 proceeds while group N's launch is being staged."""
        while True:
            with self._cv:
                if self._shutdown:
                    return
                now = time.monotonic()
                nxt = None
                for g in list(self._open.values()):
                    if g.close_at <= now:
                        self._close_locked(
                            g, "window" if g.close_at >=
                            g.window_close_at else "deadline")
                    elif nxt is None or g.close_at < nxt:
                        nxt = g.close_at
                self._cv.wait(None if nxt is None
                              else max(1e-4, nxt - now))

    def _dispatch_loop(self) -> None:
        """The hot loop: stage closed groups' launches back-to-back;
        when nothing is staged or unresolved, feed the oldest open
        group early instead of idling (module/init rationale)."""
        from ..utils import tracker
        while True:
            g = None
            # the launcher's two states, accounted in the aggregate:
            # dispatcher_idle (here, nothing ready) and group_dispatch
            # (_dispatch, from the group popped: the take of what leaves
            # with it is the hold's first work).  Idle most of a window,
            # the device starves because requests are elsewhere; busy,
            # staging is the queue
            with tracker.timed("dispatcher_idle"), self._cv:
                while not self._ready:
                    if self._shutdown:
                        return
                    if self.pipeline and self.idle_bypass and \
                            self._inflight == 0 and self._open:
                        cand = min(
                            (og for og in self._open.values()
                             if og.members),
                            key=lambda og: og.close_at, default=None)
                        if cand is not None:
                            self._close_locked(cand, "pipeline")
                            break
                    self._cv.wait()
                if self._ready:
                    g = self._ready.popleft()
            if g is not None:
                self._dispatch(g, fuse=True)

    def _take_fusable(self, g: _Group) -> list:
        """The other groups that leave with ``g``: every group that
        WAITS, closed in ``_ready`` or still collecting in ``_open``,
        whose launch the runner can fuse with ``g``'s
        (``DeviceRunner.launch_class`` equal), oldest first, up to
        ``max_group`` lanes.  A group of ``g``'s own key joins its
        lane; groups over other feeds need the kernel's lane programs
        built (``lanes_ready``) and leave alone until they are.

        Each group asked keeps what the runner resolved for it
        (``_launch_class``: its ticket rides to its staging, now or in a
        later turn).  Nothing is held back for this and nothing waits longer.  A
        closed group waits for nothing but this thread; an open one is
        closed EARLY (reason ``lanes``), as the pipeline close does
        for a device that ran dry: its window was for gathering
        members while the device is busy elsewhere, and the launch it
        was waiting to amortize is leaving now.  With nothing parked
        this is two length checks."""
        if not self._ready and not self._open:
            return []
        klass = self._launch_class(g)
        if klass is None:
            return []
        self._launch_classes.add(klass)
        with self._mu:
            waiting = list(self._ready) + [
                og for og in self._open.values() if og.members]
        keys = {g.key}
        take = []
        mismatch = 0
        ready = None        # lanes_ready, asked at most once
        for og in waiting:
            if og.key not in keys:
                if len(keys) >= self.max_group:
                    continue
                other = self._launch_class(og)
                if other != klass:
                    mismatch += other is not None
                    continue
                if ready is None:
                    ready = self._lanes_ready(g, klass)
                if not ready:
                    continue
                keys.add(og.key)
            take.append(og)
        with self._cv:
            self.lane_class_mismatch += mismatch
            for og in take:
                if not og.closed and self._open.get(og.key) is og:
                    self._close_locked(og, "lanes")
            # close() may have taken the queue meanwhile: only what is
            # still there is this thread's to dispatch
            take = [og for og in take if og in self._ready]
            for og in take:
                self._ready.remove(og)
        return take

    def _launch_class(self, g: _Group):
        """The group's launch class, asked of the runner once; what the
        runner resolved to answer stays with the group (``g.ticket``)."""
        if g.klass is not _UNASKED:
            return g.klass
        lead = g.members[0] if g.members else None
        ticket = None
        if lead is not None and not self._shutdown:
            try:
                ticket = self._runner.launch_ticket(g.key, lead.dag,
                                                    lead.storage)
            except Exception:   # noqa: BLE001 — it leaves alone
                pass
        g.ticket = ticket
        g.klass = None if ticket is None else ticket.klass
        return g.klass

    def _lanes_ready(self, g: _Group, klass) -> bool:
        try:
            return self._runner.lanes_ready(klass, g.members[0].storage)
        except Exception:       # noqa: BLE001 — they leave alone
            return False

    # ---------------------------------------------------------- dispatch

    def _dispatch(self, group: _Group, fuse: bool = False) -> None:
        """Stage one closed group's launch on whichever thread runs it:
        the dispatcher, which takes with it (``fuse``) the waiting
        groups of its launch class, or a submitter or close() at
        shutdown."""
        from ..utils import tracker
        t_begin_ns = time.perf_counter_ns()
        lead = group.members[0].tracker if group.members else None
        # the hold: every row of trace_vocab.HOLD_ROWS that runs on this
        # thread until it closes is accounted against it, and what none
        # of them covered is its ``dispatch_self``.  It opens where the
        # group was popped: the take and the look-ups it asks of the
        # runner (each group's class and ticket, a hit's only look-up)
        # are ``group_open``'s
        with tracker.hold("group_dispatch", "dispatch_self",
                          lead.trace_id if lead is not None else None), \
                tracker.held("group_open") as piece:
            merged = self._take_fusable(group) if fuse else ()
            self._launch_class(group)       # (asked, where nothing waited)
            self._stage(group, t_begin_ns, merged, piece)

    def _stage(self, group: _Group, t_begin_ns: int, merged, piece) -> None:
        """``piece``: the hold's open row, ``group_open`` on entry; it
        is turned off where the runner is called (the staging's rows are
        the runner's) and to ``group_complete`` where it returned."""
        from ..device.runner import (
            DeferredResult,
            _BatchUnavailable,
        )
        members = group.members
        # the launch's LANES: one a key, in the order the groups closed
        # (a merged group of a key already there joins that lane: its
        # members share the lane's one result, as if they had closed
        # together).  One lane, no merge: today's path.
        lanes = tickets = None
        if merged:
            by_key = {group.key: members}
            leads = {group.key: group}
            for og in merged:
                lane = by_key.get(og.key)
                if lane is None:
                    by_key[og.key] = list(og.members)
                    leads[og.key] = og
                else:
                    lane.extend(og.members)
            lanes = list(by_key.values())
            # a lane's ticket is its lead's, the first group of its key
            tickets = [g.ticket for g in leads.values()]
            members = [m for lane in lanes for m in lane]
            with self._mu:
                self.groups_merged += len(lanes) - 1
                self.same_lane_merges += len(merged) - (len(lanes) - 1)
        # resource control (resource_control.py): stacked-group
        # membership is chosen by deficit-weighted fair queuing over
        # the parked members' groups instead of FIFO — one tenant's
        # members can never monopolize a stacked dispatch.  Members
        # the DWFQ passes over are DEFERRED into the key's next
        # window (never dropped), deadline-urgent members are always
        # selected (the zero-late-acks close guarantee outranks
        # fairness), and the selection is work-conserving (throttled
        # groups ride slack lanes).  Disabled controller → one branch.
        if group.key[0] == "stack" and len(members) > 1 and \
                not self._shutdown:
            # (_shutdown re-checked under the lock in _defer_members;
            # a teardown-time group must dispatch whole — re-selecting
            # members a shutdown requeue just handed back would loop)
            from ..resource_control import GLOBAL_CONTROLLER as _rc
            if _rc.enabled:
                reserve = max(self.RESERVE_FLOOR_S,
                              8.0 * self.router.launch_ewma)
                members, deferred = _rc.select_stacked(
                    members, self.max_group,
                    window_s=self.window_s, reserve_s=reserve)
                if deferred:
                    self._defer_members(group.key, deferred)
        if lanes is None:
            lanes = [members]
        size = len(members)
        COPR_BATCH_OCCUPANCY.observe(size)
        with self._mu:
            self.groups_dispatched += 1
            self.requests_coalesced += size
            self.occupancy_sum += size
            self.max_observed_occupancy = max(
                self.max_observed_occupancy, size)
        from ..utils import tracker
        # the group's dispatch work (feed lookup, kernel cache, launch)
        # is attributed to the LEADER's TimeDetail — one member carries
        # the shared cost's phases, and ITS coalesce_wait ends where
        # this staging began (_complete), so that its phases do not
        # hold the staging twice; every member still records its own
        # coalesce_wait and resolution phases.  The explicit
        # group_dispatch span wraps the shared launch on the leader's
        # trace and is follows-from linked into every OTHER member's
        # trace (with occupancy + lane index) so "my request stacked
        # behind a group-mate" reads from any one member's trace.
        lead_tr = members[0].tracker
        # the span lives on the first SAMPLED member's trace (usually
        # the leader's) — a client-forced trace in lane 3 must not lose
        # the group correlation just because lane 0 went unsampled
        span_tr = next((m.tracker for m in members
                        if m.tracker is not None and
                        getattr(m.tracker, "sampled", False)), None)
        gsp = None
        if span_tr is not None:
            gsp = span_tr.begin("group_dispatch")
            span_tr.annotate_span(gsp, occupancy=size, lanes=len(lanes),
                                  group_kind=str(group.key[0]))
        lead_tok = tracker.adopt(
            lead_tr, parent=gsp if span_tr is lead_tr else None) \
            if lead_tr is not None else None
        # RU metering: the group's shared launch + D2H charge through
        # a GROUP context, splitting by occupancy share across member
        # tags instead of landing on the leader.  The deferred handles
        # capture this context at dispatch, so the shared fetch's
        # D2H-bytes charge splits the same way from whichever
        # completion worker joins first.
        from ..resource_metering import GLOBAL_RECORDER, region_of
        meter_members = tuple(
            (m.tag, region_of(m.storage), m.tracker) for m in members)
        t0 = time.perf_counter()
        try:
            with GLOBAL_RECORDER.group_scope(meter_members):
                if fail_point("copr::coalesce_dispatch") is not None:
                    raise _BatchUnavailable("copr::coalesce_dispatch")
                piece.turn(None)
                if len(lanes) > 1:
                    # closed groups of one launch class: ONE staging,
                    # one program, one fetch; a lane the runner could
                    # not launch comes back None and its members
                    # retry solo below
                    outcomes = self._runner.handle_lanes(
                        [(lane[0].dag, lane[0].storage) for lane in lanes],
                        tickets)
                elif group.key[0] == "stack" and size > 1:
                    handle = self._runner.handle_batched(
                        [(m.dag, m.storage) for m in members])
                    resolvers = [
                        (lambda i=i, h=handle: h.member_result(i))
                        for i in range(size)]
                    infos = [None] * size
                    outcomes = None
                else:
                    # singleton / identical-plan share: one solo
                    # dispatch, its (memoized, thread-safe) fetch
                    # serves every member
                    # (the ticket rides only where there is one: a
                    # runner that gives none takes no such argument)
                    outcomes = [self._runner.handle_request(
                        members[0].dag, members[0].storage, deferred=True,
                        **({} if group.ticket is None
                           else {"_ticket": group.ticket}))]
                # (no request waits for what follows but for its own
                # hand-over: the row and the annotation alone)
                piece.turn("group_complete", traced=False)
                if outcomes is not None:
                    resolvers, infos = [], []
                    for lane, d in zip(lanes, outcomes):
                        if isinstance(d, DeferredResult):
                            resolve, info = d.result, d.launch_info
                        elif d is None:
                            resolve = info = None       # solo, below
                        else:
                            resolve, info = (lambda r=d: r), None
                        resolvers += [resolve] * len(lane)
                        infos += [info] * len(lane)
        except Exception:   # noqa: BLE001 — incl. _BatchUnavailable
            # the batched LAUNCH failed: a failed group must never fail
            # its members — each retries as a solo dispatch (and any
            # solo failure degrades to host through the endpoint's
            # per-request contract at wait time)
            if lead_tok is not None:
                tracker.uninstall(lead_tok)
                lead_tok = None
            self.router.note_launch(time.perf_counter() - t0, size)
            piece.turn(None)    # stagings again, each with its own rows
            self._solo_fallback(members, t_begin_ns)
            return
        finally:
            if lead_tok is not None:
                tracker.uninstall(lead_tok)
            if gsp is not None:
                span_tr.end(gsp)
                for i, mm in enumerate(members):
                    mtr = mm.tracker
                    if mtr is None or mtr is span_tr or \
                            not getattr(mtr, "sampled", False):
                        continue    # the span host HAS the span itself
                    mtr.link_from("group_dispatch", span_tr.trace_id,
                                  gsp.span_id, occupancy=size, lane=i)
        self.router.note_launch(time.perf_counter() - t0, size)
        self._note_lanes(infos)
        t_staged_ns = time.perf_counter_ns()
        solo = []
        for m, resolve, info in zip(members, resolvers, infos):
            if resolve is None:
                solo.append(m)
                continue
            # the launch is in the leader's trace as the phase it was
            # (device_dispatch, under group_dispatch); every other
            # member gets it as a span of its own trace
            leads = m.tracker is lead_tr
            self._complete(m, resolve, t_begin_ns, t_staged_ns,
                           None if leads else info, leads=leads)
        if solo:
            piece.turn(None)
            self._solo_fallback(solo, t_begin_ns)

    def _note_lanes(self, infos) -> None:
        """Count the launches of one staging by their lane count, from
        the records its lanes came back with."""
        seen = {}
        for info in infos:
            if info is not None:
                seen[info["t0_ns"]] = info.get("attrs", {}).get("lanes", 1)
        if not seen:
            return
        with self._mu:
            for k in seen.values():
                self.lanes_hist[k] = self.lanes_hist.get(k, 0) + 1

    def _solo_fallback(self, members, t_begin_ns: int) -> None:
        from ..device.runner import DeferredResult
        from ..resource_metering import GLOBAL_RECORDER, region_of
        with self._mu:
            self.solo_degrade += len(members)
        for m in members:
            t_ns = time.perf_counter_ns()
            try:
                # the failed group charged nothing (no launch ran);
                # each solo retry charges ITS member's tag — never the
                # leader's, never double (exactly-once under failover)
                if m.tag is not None:
                    with GLOBAL_RECORDER.attach(
                            m.tag, requests=0,
                            region=region_of(m.storage)):
                        d = self._runner.handle_request(
                            m.dag, m.storage, deferred=True)
                else:
                    d = self._runner.handle_request(m.dag, m.storage,
                                                    deferred=True)
            except Exception as e:      # noqa: BLE001
                # surfaces at the member's wait(): the endpoint applies
                # its degrade-to-host policy there, per member
                if not m.future.done():
                    m.future.set_exception(e)
                continue
            if isinstance(d, DeferredResult):
                resolve = d.result
            else:
                resolve = (lambda r=d: r)
            self._complete(m, resolve, t_begin_ns, t_ns)

    def _defer_members(self, key, members) -> None:
        """Re-park DWFQ-deferred members into ``key``'s next
        collection window.  The member object (future, tracker, tag,
        submit time) travels whole, so its MeterContext and trace
        survive the deferral and its coalesce_wait keeps accumulating;
        the request-base RU was charged once at admission and is NOT
        re-charged on re-admission (exactly-once across deferral).
        A teardown racing the requeue dispatches inline instead —
        a parked member is never abandoned."""
        now = time.monotonic()
        reserve = max(self.RESERVE_FLOOR_S,
                      8.0 * self.router.launch_ewma)
        inline = None
        with self._cv:
            self.rc_deferrals += len(members)
            # the members return to PARKED state: the close that
            # counted them in-flight is being partially unwound
            self._inflight = max(0, self._inflight - len(members))
            for m in members:
                m.t_closed_ns = 0       # its next close sets it again
            if self._shutdown:
                g = _Group(key, now)
                g.members.extend(members)
                g.closed = True
                self._inflight += len(members)
                inline = g
            else:
                g = self._open.get(key)
                if g is None or g.closed:
                    g = _Group(key, now + self.window_s)
                    self._open[key] = g
                g.members.extend(members)
                for m in members:
                    if m.deadline_at is not None:
                        rem = m.deadline_at - now
                        g.close_at = min(
                            g.close_at, m.deadline_at - reserve,
                            now + self.WAIT_FRACTION * rem)
                if len(g.members) >= self.max_group:
                    # the size contract holds for deferral-merged
                    # groups too; the next dispatch's selection
                    # re-paces throttled surplus (and select_stacked
                    # enforces the lane bound even single-tenant)
                    self._close_locked(g, "size")
                self._cv.notify_all()   # wake BOTH loops (submit note)
        if inline is not None:
            self._dispatch(inline)

    def _complete(self, m: _Member, resolve, t_begin_ns: int,
                  t_staged_ns: int, launch: Optional[dict] = None,
                  leads: bool = False) -> None:
        """Hand the member's resolution (shared fetch join + its own
        host gather) to the completion pool; its result lands on the
        member's future for CopDeferred.wait().  ``launch``: the record
        of the launch that serves it (``DeferredResult.launch_info``),
        for a member whose own trace does not hold that launch.
        ``leads``: the staging ran under this member's tracker, which
        holds its phases (device_dispatch, a cold build's, ...)."""
        from ..resource_metering import GLOBAL_RECORDER, region_of
        from ..utils import tracker
        # a member's coalesce_wait splits at these two instants: submit
        # → closed is the collection window, closed → begin the wait
        # for this (one) dispatcher, the rest the shared staging: a
        # wait for every member but the leader, whose coalesce_wait
        # ends where the staging began (what follows is in its phases
        # as the work it was: no request's phases hold an interval
        # twice)
        t_closed_ns = m.t_closed_ns or t_begin_ns
        t_waited_ns = t_begin_ns if leads else t_staged_ns

        def task():
            tok = tracker.adopt(m.tracker) if m.tracker is not None \
                else None
            try:
                # submit → launch staged, split out of generic queue
                # time so the batched-path p99 can be decomposed from
                # the artifact; its span-only children say where: the
                # collection window, then the wait for the dispatcher
                # (what is left is the shared staging)
                sp = tracker.add_phase("coalesce_wait",
                                       t_waited_ns - m.t_submit_ns,
                                       t_waited_ns)
                tracker.add_span("coalesce_window", m.t_submit_ns,
                                 t_closed_ns, sp)
                # (0 for a group this very turn closed, ``lanes``,
                # after its staging began: add_span clamps)
                tracker.add_span("dispatch_queue_wait", t_closed_ns,
                                 t_begin_ns, sp)
                if launch is not None and sp is not None:
                    # the launch that serves this member, with its
                    # flight record (compile class, lanes, lane), where
                    # it fell inside the staging: a span of the trace
                    # alone (the wait above already covers it in
                    # phases_ms, and the aggregate has it once, from
                    # the thread that launched)
                    mtr = m.tracker
                    dsp = mtr.begin("device_dispatch", sp,
                                    launch["t0_ns"])
                    mtr.end(dsp, launch["t1_ns"])
                    mtr.annotate_span(dsp, **launch.get("attrs", {}))
                # group_fetch_wait: this member's join of the group's
                # shared (memoized) fetch — for the first joiner it
                # nests the real d2h_wait/host_materialize spans, for
                # the rest it IS the wait on the memo
                with tracker.span("group_fetch_wait"):
                    if m.tag is not None:
                        with GLOBAL_RECORDER.attach(
                                m.tag, requests=0,
                                region=region_of(m.storage)):
                            return resolve()
                    return resolve()
            finally:
                if tok is not None:
                    tracker.uninstall(tok)

        def run_and_set():
            try:
                r = task()
            except BaseException as e:  # noqa: BLE001 — ride the future
                if not m.future.done():
                    m.future.set_exception(e)
                return
            if not m.future.done():
                m.future.set_result(r)

        pool = None
        if self._endpoint is not None:
            pool = self._endpoint._completion()
        if pool is None:
            run_and_set()
            return
        f = pool.submit(run_and_set)
        if f.done() and f.exception() is not None and \
                not m.future.done():
            # completion pool already shut down: the submit was refused
            # synchronously — surface it so the waiter host-degrades
            m.future.set_exception(f.exception())

    # ----------------------------------------------------------- teardown

    def close(self) -> None:
        """Stop collecting; dispatch every still-open group (their
        members are parked waiters that must resolve — flush, never
        abandon) and join the dispatcher."""
        with self._cv:
            self._shutdown = True
            for g in list(self._open.values()):
                self._close_locked(g, "shutdown")
            self._cv.notify_all()
            threads = [self._thread, self._dispatcher]
        for t in threads:
            if t is not None:
                t.join(timeout=5.0)
        # belt and braces for stop-under-load: if the dispatcher died
        # (or the join timed out) with groups still queued, dispatch
        # them inline — a parked member's future must NEVER be left
        # unresolved by teardown, or its waiter hangs forever
        with self._mu:
            leftovers = list(self._ready)
            self._ready.clear()
        for g in leftovers:
            self._dispatch(g)

    # -------------------------------------------------------------- stats

    def stats(self) -> dict:
        with self._mu:
            groups = self.groups_dispatched
            out = {
                "enabled": self.enabled,
                "window_ms": round(self.window_s * 1e3, 3),
                "max_group": self.max_group,
                "open_groups": len(self._open),
                "inflight": self._inflight,
                "groups_dispatched": groups,
                "requests_coalesced": self.requests_coalesced,
                "mean_occupancy": round(
                    self.occupancy_sum / groups, 3) if groups else 0.0,
                "max_occupancy": self.max_observed_occupancy,
                "solo_degrade": self.solo_degrade,
                "rc_deferrals": self.rc_deferrals,
                "closes": dict(self.closes),
                "plan_share_groups": self.plan_share_groups,
                "plan_share_hits": self.plan_share_hits,
                "lanes_hist": {str(k): n for k, n in
                               sorted(self.lanes_hist.items())},
                "multi_lane_launches": sum(
                    n for k, n in self.lanes_hist.items() if k > 1),
                "lanes_sum": sum(k * n
                                 for k, n in self.lanes_hist.items()),
                "groups_merged": self.groups_merged,
                "same_lane_merges": self.same_lane_merges,
                "lane_class_mismatch": self.lane_class_mismatch,
                "launch_classes": len(self._launch_classes),
            }
        lane_stats = getattr(self._runner, "lane_stats", None)
        out["unbuilt_fallbacks"] = lane_stats()["unbuilt_fallbacks"] \
            if lane_stats is not None else 0
        out["router"] = self.router.stats()
        return out
