"""Admission-control tests (ISSUE 9): per-client fairness, deadline
propagation, retry budgets, and per-pod breakers under overload.

Three layers, cheapest first:

- pure units (tier-1, milliseconds): token-bucket arithmetic on an
  injected clock, WFQ grant ordering, retry-budget deposits/withdrawals,
  breaker state machine, rendezvous replica agreement, the engine's
  priority-aware backlog insert — the fairness MATH, no HTTP anywhere;
- ``FakePod`` HTTP drills (tier-1, fast): the router stamps shrinking
  ``X-ModelX-Deadline-Ms`` budgets across failover attempts, honors an
  incoming clamp, stops failover when the retry budget runs dry, and
  skips/recovers pods through the 5xx breaker;
- the real-pod overload storm (``slow`` + ``chaos``): 3 clients (one
  10x hotter) against 2 pods with a seeded mid-storm ``PodKillSwitch``
  — fair-share occupancy bounds per client, zero dropped non-streaming
  requests, bounded upstream attempts (no retry amplification).
"""

import threading
import time

import pytest
import requests

from modelx_tpu.dl.serving_errors import QueueFullError
from modelx_tpu.router.admission import (
    DEADLINE_HEADER,
    PRIORITY_HEADER,
    AdmissionController,
    BreakerBoard,
    RetryBudget,
    TokenBucket,
    client_key,
    jain_index,
    parse_priority,
)
from modelx_tpu.router.policy import (
    HRW_LOAD_SLACK,
    StickyTable,
    plan_route,
    rendezvous_pod,
    sticky_keys,
)
from modelx_tpu.router.registry import PodRegistry, PodState
from modelx_tpu.router.server import FleetRouter, route_serve
from modelx_tpu.registry.server import free_port
from modelx_tpu.testing.faults import PodKillSwitch

from test_router import FakePod, make_router, wait_for


class FakeClock:
    """Deterministic monotonic stand-in for the bucket/breaker units."""

    def __init__(self, t: float = 100.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


# -- pure units: the fairness math ---------------------------------------------


class TestTokenBucket:
    def test_burst_then_refill(self):
        clk = FakeClock()
        b = TokenBucket(rate=1.0, burst=2.0, clock=clk)
        assert b.take() and b.take()
        assert not b.take()  # burst spent
        clk.advance(1.0)
        assert b.take()      # one token refilled
        assert not b.take()

    def test_refill_caps_at_burst(self):
        clk = FakeClock()
        b = TokenBucket(rate=10.0, burst=3.0, clock=clk)
        clk.advance(100.0)
        assert b.level() == 3.0

    def test_disabled_rate_always_takes(self):
        b = TokenBucket(rate=0.0)
        assert all(b.take() for _ in range(1000))

    def test_wait_estimate(self):
        clk = FakeClock()
        b = TokenBucket(rate=2.0, burst=1.0, clock=clk)
        assert b.take()
        assert abs(b.wait_s() - 0.5) < 1e-9  # 1 token at 2/s
        clk.advance(0.5)
        assert b.wait_s() == 0.0


class TestRetryBudget:
    def test_disabled_allows_everything(self):
        rb = RetryBudget(ratio=0.0)
        assert all(rb.allow_retry() for _ in range(50))
        assert rb.snapshot()["retries_denied"] == 0

    def test_reserve_then_deposits(self):
        rb = RetryBudget(ratio=0.5, reserve=2.0)
        assert rb.allow_retry() and rb.allow_retry()
        assert not rb.allow_retry()  # reserve spent, nothing deposited
        for _ in range(4):           # 4 first attempts x 0.5 = 2 tokens
            rb.record_attempt()
        assert rb.allow_retry() and rb.allow_retry()
        assert not rb.allow_retry()
        snap = rb.snapshot()
        assert snap["retries_denied"] == 2
        assert snap["requests_total"] == 4

    def test_cap_bounds_banked_tokens(self):
        rb = RetryBudget(ratio=1.0, reserve=0.0, cap=3.0)
        for _ in range(100):
            rb.record_attempt()
        assert rb.snapshot()["tokens"] == 3.0


class TestBreakerBoard:
    def test_threshold_opens_and_probe_recovers(self):
        clk = FakeClock()
        bb = BreakerBoard(threshold=3, cooldown_s=5.0, clock=clk)
        for _ in range(2):
            bb.record("p", ok=False)
        bb.record("p", ok=True)      # success resets the streak
        assert bb.allow("p")
        for _ in range(3):
            bb.record("p", ok=False)
        assert not bb.allow("p")     # OPEN
        assert bb.snapshot()["pods"]["p"]["state"] == "open"
        clk.advance(5.0)
        assert bb.allow("p")         # half-open: the one probe
        assert not bb.allow("p")     # second caller blocked while probing
        bb.record("p", ok=True)
        assert bb.snapshot()["pods"]["p"]["state"] == "closed"
        assert bb.allow("p")

    def test_probe_failure_reopens(self):
        clk = FakeClock()
        bb = BreakerBoard(threshold=1, cooldown_s=2.0, clock=clk)
        bb.record("p", ok=False)
        clk.advance(2.0)
        assert bb.allow("p")
        bb.record("p", ok=False)
        assert not bb.allow("p")
        assert bb.snapshot()["pods"]["p"]["opens"] == 2

    def test_probe_lease_expires(self):
        # a caller that took the probe slot but never dispatched (its
        # deadline/retry budget ran out first) must not wedge the pod
        clk = FakeClock()
        bb = BreakerBoard(threshold=1, cooldown_s=1.0, clock=clk)
        bb.record("p", ok=False)
        clk.advance(1.0)
        assert bb.allow("p")   # probe taken, outcome never recorded
        clk.advance(1.0)
        assert bb.allow("p")   # lease expired: a new probe may go

    def test_observe_only_counts_would_open(self):
        bb = BreakerBoard(threshold=0)
        for _ in range(BreakerBoard.OBSERVE_THRESHOLD):
            bb.record("p", ok=False)
        assert bb.allow("p")  # never blocks
        assert bb.snapshot()["pods"]["p"]["would_open"] == 1
        assert bb.snapshot()["pods"]["p"]["opens"] == 0

    def test_forget_clears_state(self):
        bb = BreakerBoard(threshold=1)
        bb.record("p", ok=False)
        assert not bb.allow("p")
        bb.forget("p")  # quarantine owns recovery now
        assert bb.allow("p")


class TestClientKeying:
    def test_bearer_token_is_hashed_never_leaked(self):
        key = client_key({"Authorization": "Bearer sekrit-token"},
                         ("1.2.3.4", 9))
        assert key.startswith("tok:") and "sekrit" not in key
        # same token -> same identity; different token -> different
        assert key == client_key({"Authorization": "Bearer sekrit-token"},
                                 ("5.6.7.8", 1))
        assert key != client_key({"Authorization": "Bearer other"}, None)

    def test_header_then_ip_fallback(self):
        assert client_key({"X-ModelX-Client": "svc-a"},
                          ("1.2.3.4", 9)) == "hdr:svc-a"
        assert client_key({}, ("1.2.3.4", 9)) == "ip:1.2.3.4"
        assert client_key({}, None) == "ip:unknown"

    def test_parse_priority(self):
        assert parse_priority("batch") == "batch"
        assert parse_priority(" Batch ") == "batch"
        for v in (None, "", "interactive", "urgent"):
            assert parse_priority(v) == "interactive"


class TestJainIndex:
    def test_math(self):
        assert jain_index([5, 5]) == 1.0
        assert jain_index([1, 0]) == 0.5
        assert jain_index([10, 1]) == pytest.approx(0.599, abs=0.001)
        assert jain_index([]) is None
        assert jain_index([0, 0]) is None


class TestAdmissionController:
    def test_observe_only_never_blocks_but_accounts(self):
        ac = AdmissionController()  # all knobs 0
        for _ in range(5):
            ac.admit("c")
        snap = ac.snapshot()
        assert snap["enabled"] is False
        assert snap["clients"]["c"]["admitted"] == 5
        assert snap["clients"]["c"]["inflight"] == 5
        for _ in range(5):
            ac.release("c")
        assert ac.snapshot()["inflight"] == 0

    def test_client_rate_ceiling_sheds_with_retry_after(self):
        clk = FakeClock()
        ac = AdmissionController(client_rate=1.0, clock=clk)
        ac.admit("c")
        ac.admit("c")  # burst = 2x rate
        with pytest.raises(QueueFullError) as ei:
            ac.admit("c")
        assert ei.value.http_status == 429
        assert int(ei.value.headers()["Retry-After"]) >= 1
        clk.advance(1.0)
        ac.admit("c")  # refilled
        assert ac.snapshot()["shed_by_class"]["interactive"] == 1

    def test_inline_admit_below_fair_share(self):
        ac = AdmissionController(fair_share=2)
        ac.admit("a")
        ac.admit("b")
        assert ac.snapshot()["inflight"] == 2
        assert ac.snapshot()["backlog"] == 0

    def _spawn_waiter(self, ac, key, order, priority="interactive",
                      deadline=None):
        def run():
            try:
                ac.admit(key, priority=priority, deadline=deadline)
                order.append(("granted", key))
            except QueueFullError:
                order.append(("shed", key))

        t = threading.Thread(target=run, daemon=True)
        t.start()
        return t

    def test_wfq_grants_starved_client_before_heavy_backlog(self):
        """fair_share=1, one slot busy. The hot client queues 3 waiters
        FIRST, the cold client 1 waiter LAST — strict FIFO would serve
        cold 4th; the fair scheduler serves cold before hot's 2nd."""
        ac = AdmissionController(fair_share=1)
        ac.admit("hot")  # occupy the slot (charges hot's virtual pass)
        order: list = []
        threads = []
        for _ in range(3):
            threads.append(self._spawn_waiter(ac, "hot", order))
        wait_for(lambda: ac.snapshot()["backlog"] == 3)
        threads.append(self._spawn_waiter(ac, "cold", order))
        wait_for(lambda: ac.snapshot()["backlog"] == 4)
        for _ in range(5):  # release the slot until everyone ran
            ac.release(order[-1][1] if order else "hot")
            wait_for(lambda: ac.snapshot()["backlog"] < 4 or order)
            time.sleep(0.02)
        for t in threads:
            t.join(timeout=5)
        granted = [k for verdict, k in order if verdict == "granted"]
        assert sorted(granted) == ["cold", "hot", "hot", "hot"]
        # the starved client was NOT last despite arriving last
        assert granted.index("cold") < 2, granted

    def test_full_backlog_sheds_batch_first(self):
        ac = AdmissionController(fair_share=1, max_backlog=1)
        ac.admit("a")  # slot busy
        order: list = []
        t_batch = self._spawn_waiter(ac, "b", order, priority="batch")
        wait_for(lambda: ac.snapshot()["backlog"] == 1)
        # interactive arrival evicts the queued batch waiter
        t_int = self._spawn_waiter(ac, "c", order, priority="interactive")
        t_batch.join(timeout=5)
        assert ("shed", "b") in order
        assert ac.snapshot()["evicted_batch_total"] == 1
        # and a BATCH arrival at a full backlog sheds itself
        with pytest.raises(QueueFullError):
            ac.admit("d", priority="batch")
        assert ac.snapshot()["shed_by_class"]["batch"] == 2
        ac.release("a")
        t_int.join(timeout=5)
        assert ("granted", "c") in order

    def test_full_backlog_displaces_most_backlogged_client(self):
        """A hot client's thread count must not own the whole backlog:
        an arrival holding fewer waiters than its share displaces the
        most-backlogged client's newest waiter instead of shedding at
        the door (the FIFO monopoly, one layer up)."""
        ac = AdmissionController(fair_share=1, max_backlog=3)
        ac.admit("hot")  # slot busy
        order: list = []
        threads = [self._spawn_waiter(ac, "hot", order) for _ in range(3)]
        wait_for(lambda: ac.snapshot()["backlog"] == 3)  # backlog full
        cold = self._spawn_waiter(ac, "cold", order)
        # one hot waiter was displaced (shed), cold is queued in its place
        wait_for(lambda: ("shed", "hot") in order)
        snap = ac.snapshot()
        assert snap["backlog"] == 3
        assert snap["clients"]["cold"]["waiting"] == 1
        assert snap["shed_by_class"]["interactive"] == 1
        # and ANOTHER cold arrival does not displace further: with 1 of
        # 3 waiters cold already holds its share against hot's 2
        with pytest.raises(QueueFullError):
            ac.admit("cold", deadline=time.monotonic())
        for _ in range(4):
            ac.release("hot")
            time.sleep(0.02)
        for t in threads + [cold]:
            t.join(timeout=5)
        assert ("granted", "cold") in order

    def test_queued_deadline_expiry_is_504_not_shed(self):
        """A caller whose OWN budget runs out while queued gets the
        deadline 504 (the status the routing loop would answer a moment
        later), not an overload 429 — clients keying retries on
        429-vs-504 must see one semantic for one condition."""
        from modelx_tpu.dl.serving_errors import DeadlineExceededError

        ac = AdmissionController(fair_share=1)
        ac.admit("a")
        t0 = time.monotonic()
        with pytest.raises(DeadlineExceededError) as ei:
            ac.admit("b", deadline=time.monotonic() + 0.15, budget_s=0.15)
        assert "0.15s" in str(ei.value)
        assert 0.1 < time.monotonic() - t0 < 5.0
        snap = ac.snapshot()
        assert snap["backlog"] == 0       # the waiter withdrew
        assert snap["expired_total"] == 1
        assert snap["shed_total"] == 0    # not an overload shed

    def test_sub_one_client_rate_still_admits(self):
        """--client-rate below 0.5 must not shed forever: the bucket's
        capacity floors at one whole token (rate 0.25 x burst 2 = 0.5
        capacity could never satisfy take(1.0))."""
        clk = FakeClock()
        ac = AdmissionController(client_rate=0.25, clock=clk)
        ac.admit("c")  # the floored one-token burst
        with pytest.raises(QueueFullError):
            ac.admit("c")
        clk.advance(4.0)  # one token at 0.25/s
        ac.admit("c")


class TestEnginePriorityBacklog:
    """The engine-side half of priority classes: interactive items queue
    ahead of batch items at the admission boundary (pure insert-order
    unit — no model, no engine thread)."""

    def _item(self, tag, priority=None, restart=False):
        from modelx_tpu.dl.continuous import _Ticket

        samp = {"seed": tag}
        if priority:
            samp["priority"] = priority
        ticket = _Ticket()
        ticket.restart = restart
        return ([1, 2, 3], 4, samp, ticket)

    def _fresh(self):
        from modelx_tpu.dl.continuous import ContinuousBatcher

        cb = object.__new__(ContinuousBatcher)
        cb._waiting = []
        return cb

    def test_interactive_queues_ahead_of_batch(self):
        cb = self._fresh()
        b1 = self._item(1, "batch")
        i1 = self._item(2)
        b2 = self._item(3, "batch")
        i2 = self._item(4, "interactive")
        for item in (b1, i1, b2, i2):
            cb._backlog_insert(item)
        assert cb._waiting == [i1, i2, b1, b2]

    def test_restart_pinned_fill_is_never_jumped(self):
        """A preempted fill re-queued at the backlog head (exact-restart
        guarantee, re-grab livelock guard) must not be cut in front of
        by an interactive arrival, even when the fill is batch-class."""
        cb = self._fresh()
        pinned = self._item(1, "batch", restart=True)
        cb._waiting.append(pinned)  # _requeue_preempted splices at head
        i1 = self._item(2)
        b1 = self._item(3, "batch")
        i2 = self._item(4)
        for item in (i1, b1, i2):
            cb._backlog_insert(item)
        # the pin holds the head; interactive still beats the TAIL batch
        assert cb._waiting == [pinned, i1, i2, b1]

    def test_all_interactive_stays_fifo(self):
        cb = self._fresh()
        items = [self._item(i) for i in range(4)]
        for item in items:
            cb._backlog_insert(item)
        assert cb._waiting == items  # plain append: order preserved

    def test_all_batch_stays_fifo(self):
        cb = self._fresh()
        items = [self._item(i, "batch") for i in range(3)]
        for item in items:
            cb._backlog_insert(item)
        assert cb._waiting == items


class TestRendezvousAgreement:
    def _pod(self, url, depth=0):
        return PodState(url, healthy=True,
                        models={"m": {"state": "READY"}},
                        serving={"m": {"queue_depth": depth}})

    def test_two_replicas_agree_without_shared_state(self):
        """Two independently-built routers (fresh sticky tables, shuffled
        candidate order) pick the SAME anchor pod for the same prefix —
        the >1-router-replica consistency the sticky table alone cannot
        give (ROADMAP item)."""
        pods_a = [self._pod(u) for u in ("x", "y", "z")]
        pods_b = [self._pod(u) for u in ("z", "x", "y")]  # shuffled build
        for seed in range(20):
            req = {"tokens": [[seed + 1] * 8]}
            keys = sticky_keys("m", req, "/v1/generate")
            plan_a = plan_route("m", pods_a, StickyTable(), keys, {})
            plan_b = plan_route("m", pods_b, StickyTable(), keys, {})
            assert plan_a[0].url == plan_b[0].url, f"seed {seed}"

    def test_different_prefixes_spread_across_pods(self):
        pods = [self._pod(u) for u in ("x", "y", "z")]
        anchors = set()
        for seed in range(30):
            keys = sticky_keys("m", {"tokens": [[seed + 1] * 8]},
                               "/v1/generate")
            anchors.add(rendezvous_pod(keys[-1], pods).url)
        assert len(anchors) == 3  # HRW spreads, it does not pile up

    def test_anchor_is_bounded_load(self):
        """An anchor whose queue is HRW_LOAD_SLACK+ deeper than the
        least-loaded pod loses to load order (no hot-prefix pile-up)."""
        keys = sticky_keys("m", {"tokens": [[7] * 8]}, "/v1/generate")
        flat = [self._pod(u) for u in ("x", "y", "z")]
        anchor_url = rendezvous_pod(keys[-1], flat).url
        pods = [self._pod(u, depth=(HRW_LOAD_SLACK + 1
                                    if u == anchor_url else 0))
                for u in ("x", "y", "z")]
        plan = plan_route("m", pods, StickyTable(), keys, {})
        assert plan[0].url != anchor_url
        # within the slack the anchor still wins (replica agreement)
        pods = [self._pod(u, depth=(HRW_LOAD_SLACK if u == anchor_url else 0))
                for u in ("x", "y", "z")]
        plan = plan_route("m", pods, StickyTable(), keys, {})
        assert plan[0].url == anchor_url

    def test_keyless_requests_route_by_load(self):
        pods = [self._pod("b", 5), self._pod("a", 1), self._pod("c", 0)]
        plan = plan_route("m", pods, StickyTable(), [], {})
        assert [p.url for p in plan] == ["c", "a", "b"]


# -- FakePod HTTP drills -------------------------------------------------------


class TestDeadlinePropagationHTTP:
    def test_attempts_carry_shrinking_budget(self):
        """The deadline-correctness fix (ISSUE 9 satellite): every
        upstream attempt is stamped with the REMAINING budget, so a
        failover attempt never restarts the clock — total upstream work
        respects the original --request-timeout."""
        slow_shedder = FakePod()
        slow_shedder.post_status = 503
        slow_shedder.post_delay_s = 0.3
        backup = FakePod()
        backup.serving = {"default": {"queue_depth": 99}}  # always 2nd
        rt = make_router([slow_shedder.url, backup.url])
        try:
            r = requests.post(rt.base + "/v1/generate",
                              json={"tokens": [[1, 2, 3, 4]]})
            assert r.status_code == 200
            first = int(slow_shedder.seen_headers[0][DEADLINE_HEADER.lower()])
            second = int(backup.seen_headers[0][DEADLINE_HEADER.lower()])
            # the router's whole budget is 10s (make_router); attempt 1
            # gets <= that, attempt 2 gets <= attempt 1 minus the 300ms
            # the first pod burned — never a fresh full timeout
            assert first <= 10_000
            assert second <= first - 250, (first, second)
        finally:
            rt.httpd.shutdown()
            slow_shedder.close()
            backup.close()

    def test_incoming_deadline_clamps_router_budget(self):
        pod = FakePod()
        rt = make_router([pod.url])
        try:
            r = requests.post(rt.base + "/v1/generate",
                              json={"tokens": [[1, 2, 3, 4]]},
                              headers={DEADLINE_HEADER: "500"})
            assert r.status_code == 200
            stamped = int(pod.seen_headers[0][DEADLINE_HEADER.lower()])
            assert stamped <= 500  # the smaller caller budget won
            # malformed header: the router's own budget stands
            r = requests.post(rt.base + "/v1/generate",
                              json={"tokens": [[1, 2, 3, 4]]},
                              headers={DEADLINE_HEADER: "bogus"})
            assert r.status_code == 200
            assert int(pod.seen_headers[1][DEADLINE_HEADER.lower()]) <= 10_000
        finally:
            rt.httpd.shutdown()
            pod.close()

    def test_priority_class_propagates(self):
        pod = FakePod()
        rt = make_router([pod.url])
        try:
            requests.post(rt.base + "/v1/generate",
                          json={"tokens": [[1, 2, 3, 4]]},
                          headers={PRIORITY_HEADER: "batch"})
            requests.post(rt.base + "/v1/generate",
                          json={"tokens": [[1, 2, 3, 4]]})
            assert pod.seen_headers[0][PRIORITY_HEADER.lower()] == "batch"
            assert pod.seen_headers[1][PRIORITY_HEADER.lower()] == "interactive"
        finally:
            rt.httpd.shutdown()
            pod.close()


class TestRetryBudgetHTTP:
    def test_empty_budget_stops_failover_relays_last_backpressure(self):
        """Brownout: every pod sheds. With a drained retry budget the
        router makes ONE upstream attempt and relays ITS backpressure —
        no amplification exactly when the fleet is weakest."""
        pods = [FakePod() for _ in range(3)]
        for p in pods:
            p.post_status = 503
            p.post_headers = {"Retry-After": "5"}
        rt = make_router([p.url for p in pods],
                         retry_budget=RetryBudget(ratio=0.1, reserve=0.0))
        try:
            r = requests.post(rt.base + "/v1/generate",
                              json={"tokens": [[1, 2, 3, 4]]})
            assert r.status_code == 503
            assert r.headers.get("Retry-After") == "5"
            snap = rt.router.metrics.snapshot()
            assert snap["upstream_attempts_total"] == 1
            assert snap["retry_budget_exhausted_total"] == 1
            assert sum(len(p.requests) for p in pods) == 1
        finally:
            rt.httpd.shutdown()
            for p in pods:
                p.close()

    def test_healthy_traffic_banks_failover_tokens(self):
        shedder = FakePod()
        healthy = FakePod()
        healthy.serving = {"default": {"queue_depth": 99}}  # always 2nd
        rt = make_router([shedder.url, healthy.url],
                         retry_budget=RetryBudget(ratio=0.5, reserve=0.0))
        try:
            body = {"tokens": [[1, 2, 3, 4]]}
            # bank tokens with 4 healthy first attempts (0.5 each)
            for _ in range(4):
                assert requests.post(rt.base + "/v1/generate",
                                     json=body).status_code == 200
            shedder.post_status = 503
            r = requests.post(rt.base + "/v1/generate", json=body)
            assert r.status_code == 200  # failover spent a banked token
            assert r.json()["pod"] == healthy.url
            assert rt.router.retry_budget.snapshot()["retries_allowed"] == 1
        finally:
            rt.httpd.shutdown()
            shedder.close()
            healthy.close()


class TestBreakerHTTP:
    def test_5xx_burst_opens_then_probe_recovers(self):
        flaky = FakePod()
        flaky.status_script = [500, 500, 200, 200]  # sick, then healed
        backup = FakePod()
        backup.serving = {"default": {"queue_depth": 99}}  # always 2nd
        # the breaker's own clock, moved by hand: with the wall clock and a
        # 0.2 s cooldown, a loaded machine took longer than that between the
        # breaker's opening and the next request, which then went to the
        # flaky pod as the half-open probe and not to the backup
        now = [1000.0]
        rt = make_router([flaky.url, backup.url],
                         breakers=BreakerBoard(threshold=2, cooldown_s=30.0,
                                               clock=lambda: now[0]))
        try:
            body = {"tokens": [[1, 2, 3, 4]]}
            # two 500s relay verbatim (4xx/5xx are deterministic answers)
            # and feed the breaker
            assert requests.post(rt.base + "/v1/generate",
                                 json=body).status_code == 500
            assert requests.post(rt.base + "/v1/generate",
                                 json=body).status_code == 500
            # breaker accounting lands a beat after the client has its
            # bytes (the handler records post-relay): wait, don't race
            wait_for(lambda: rt.router.snapshot()
                     ["breakers"]["pods"][flaky.url]["state"] == "open")
            # OPEN: the flaky pod is skipped, backup serves
            r = requests.post(rt.base + "/v1/generate", json=body)
            assert r.status_code == 200 and r.json()["pod"] == backup.url
            assert rt.router.metrics.snapshot()["breaker_skipped_total"] >= 1
            assert len(flaky.requests) == 2
            now[0] += 30.5  # cooldown -> half-open
            # the probe goes to the flaky pod, succeeds, and closes it
            # (fresh prompt: the 200 above sticky-pinned `body`'s
            # conversation to the backup pod — which is the point of
            # stickiness, but this request must exercise the plan order)
            r = requests.post(rt.base + "/v1/generate",
                              json={"tokens": [[9, 9, 9, 9]]})
            assert r.status_code == 200 and r.json()["pod"] == flaky.url
            snap = rt.router.snapshot()
            assert snap["breakers"]["pods"][flaky.url]["state"] == "closed"
        finally:
            rt.httpd.shutdown()
            flaky.close()
            backup.close()

    def test_deadline_504s_never_trip_the_breaker(self):
        """A pod expiring requests whose PROPAGATED budget ran out is
        honoring the deadline contract, not malfunctioning — routine
        504s from tight caller deadlines must not open its breaker."""
        pod = FakePod()
        pod.status_script = [504, 504, 504, 504]
        rt = make_router([pod.url],
                         breakers=BreakerBoard(threshold=2, cooldown_s=60.0))
        try:
            for _ in range(4):
                r = requests.post(rt.base + "/v1/generate",
                                  json={"tokens": [[1, 2, 3, 4]]})
                assert r.status_code == 504
            wait_for(lambda: len(pod.requests) == 4)
            board = rt.router.snapshot()["breakers"]["pods"]
            state = board.get(pod.url, {"state": "closed"})
            assert state["state"] == "closed"
        finally:
            rt.httpd.shutdown()
            pod.close()

    def test_backpressure_never_trips_the_breaker(self):
        shedder = FakePod()
        shedder.post_status = 429
        shedder.post_headers = {"Retry-After": "1"}
        rt = make_router([shedder.url],
                         breakers=BreakerBoard(threshold=2, cooldown_s=60.0))
        try:
            for _ in range(5):
                r = requests.post(rt.base + "/v1/generate",
                                  json={"tokens": [[1, 2, 3, 4]]})
                assert r.status_code == 429
            board = rt.router.snapshot()["breakers"]["pods"]
            state = board.get(shedder.url, {"state": "closed"})
            assert state["state"] == "closed"
            assert state.get("consecutive_failures", 0) == 0
        finally:
            rt.httpd.shutdown()
            shedder.close()


class TestAdmissionHTTP:
    def test_client_rate_ceiling_sheds_typed_429(self):
        pod = FakePod()
        rt = make_router(
            [pod.url],
            admission=AdmissionController(client_rate=1.0),
        )
        try:
            statuses = [
                requests.post(rt.base + "/v1/generate",
                              json={"tokens": [[1, 2, 3, 4]]},
                              headers={"X-ModelX-Client": "greedy"}).status_code
                for _ in range(6)
            ]
            assert statuses.count(200) >= 2  # the burst allowance
            assert 429 in statuses
            shed = requests.post(rt.base + "/v1/generate",
                                 json={"tokens": [[1, 2, 3, 4]]},
                                 headers={"X-ModelX-Client": "greedy"})
            if shed.status_code == 429:
                assert "Retry-After" in shed.headers
                # the shed names its real cause (the rate ceiling),
                # not a backlog that is not even enabled
                assert "rate exceeds the ceiling" in shed.json()["error"]
            snap = rt.router.snapshot()["admission"]
            assert snap["clients"]["hdr:greedy"]["shed"] >= 1
            assert rt.router.metrics.snapshot()["admission_shed_total"] >= 1
            # a DIFFERENT client is not rate-limited by greedy's bucket
            r = requests.post(rt.base + "/v1/generate",
                              json={"tokens": [[1, 2, 3, 4]]},
                              headers={"X-ModelX-Client": "polite"})
            assert r.status_code == 200
        finally:
            rt.httpd.shutdown()
            pod.close()

    def test_observe_only_defaults_change_nothing(self):
        """The acceptance guard: with default knobs an unsaturated fleet
        shows no behavior change — every request admits instantly, and
        the admission layer only accounts."""
        pod = FakePod()
        rt = make_router([pod.url])  # all admission knobs at defaults
        try:
            for _ in range(8):
                r = requests.post(rt.base + "/v1/generate",
                                  json={"tokens": [[1, 2, 3, 4]]})
                assert r.status_code == 200
            snap = rt.router.snapshot()["admission"]
            assert snap["enabled"] is False
            assert snap["shed_total"] == 0 and snap["backlog"] == 0
            assert sum(c["admitted"] for c in snap["clients"].values()) == 8
        finally:
            rt.httpd.shutdown()
            pod.close()


# -- real pods: deadline clamp inside the engine -------------------------------


@pytest.fixture(scope="module")
def engine_pod():
    """One real pod with the continuous engine on a tiny model: the
    deadline-propagation acceptance runs against real submit/expiry
    machinery, not a scripted fake."""
    from test_router import write_tiny
    from modelx_tpu.dl.serve import ModelServer, ServerSet, serve
    import tempfile

    d = tempfile.mkdtemp(prefix="admission-model-")
    write_tiny(d)
    server = ModelServer(d, mesh_spec="dp=1", max_seq_len=128, name="default")
    server.load()
    sset = ServerSet({"default": server}, continuous_batch=True, max_slots=2,
                     request_timeout_s=30.0)
    sset.pool.mark_ready("default")
    httpd = serve(sset, listen=f"127.0.0.1:{free_port()}")
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    yield {"sset": sset, "httpd": httpd, "url": url, "server": server}
    httpd.shutdown()
    cb = sset.cbatchers.get("default")
    if cb is not None:
        cb.close()
        cb.release_device_state()


class TestPodHonorsDeadline:
    def test_expired_budget_is_504_before_any_work(self, engine_pod):
        r = requests.post(engine_pod["url"] + "/v1/generate",
                          json={"tokens": [[1, 2, 3]], "max_new_tokens": 4},
                          headers={DEADLINE_HEADER: "0"})
        assert r.status_code == 504
        assert "deadline" in r.json()["error"]

    def test_expired_budget_is_504_openai_shape(self, engine_pod):
        r = requests.post(engine_pod["url"] + "/v1/completions",
                          json={"model": "default", "prompt": "hi",
                                "max_tokens": 4},
                          headers={DEADLINE_HEADER: "0"})
        assert r.status_code == 504
        err = r.json()["error"]
        assert "deadline" in err["message"]

    def test_engine_clamps_to_propagated_remainder(self, engine_pod):
        """submit(timeout_s=...) clamps below the engine's own 30s
        --request-timeout: the ticket expires on the PROPAGATED budget."""
        cb = engine_pod["sset"].continuous_for(engine_pod["server"])
        ticket = cb.submit([1, 2, 3], 4, {"temperature": 0.0},
                           timeout_s=0.5)
        assert ticket.deadline is not None
        assert ticket.timeout_s == 0.5  # min(30, 0.5)
        # and without a propagated budget the engine default stands
        t2 = cb.submit([1, 2, 3], 4, {"temperature": 0.0})
        assert t2.timeout_s == 30.0
        ticket.cancel()
        t2.cancel()

    def test_tiny_budget_expires_in_engine_not_fresh_clock(self, engine_pod):
        """A 1ms propagated budget reaches the engine and expires at the
        first boundary — the pod does NOT substitute its own 30s."""
        r = requests.post(engine_pod["url"] + "/v1/generate",
                          json={"tokens": [[1, 2, 3]],
                                "max_new_tokens": 64},
                          headers={DEADLINE_HEADER: "1"},
                          timeout=20)
        # either the handler caught it already expired (504 fast) or the
        # engine expired the ticket at a boundary (504 typed) — never a
        # 200 produced long after the caller's budget died
        assert r.status_code == 504
        assert "deadline" in r.json()["error"]


# -- the overload storm (slow + chaos) -----------------------------------------


@pytest.mark.slow
@pytest.mark.chaos
class TestOverloadStorm:
    def test_fair_storm_with_pod_kill(self):
        """ISSUE 9 acceptance drill: 3 clients — one 10x hotter — storm
        2 pods through an admission-enabled router while a seeded
        PodKillSwitch kills one pod mid-storm. Asserts: (1) per-client
        fair-share occupancy bounds (the hot client cannot monopolize:
        cold clients' goodput share stays near equal), (2) zero dropped
        non-streaming requests (every answer is a 200 with the expected
        deterministic tokens or a typed 429/503/504 — no transport
        errors, no silent drops), (3) bounded upstream attempts per
        logical request (the retry budget holds: no amplification)."""
        from test_router import new_pod, write_tiny
        from modelx_tpu.dl.serve import ModelServer
        import tempfile

        d = tempfile.mkdtemp(prefix="storm-model-")
        write_tiny(d)
        server = ModelServer(d, mesh_spec="dp=1", max_seq_len=128,
                             name="default")
        server.load()
        pods = [new_pod(server) for _ in range(2)]
        kills = {p.url: PodKillSwitch(p.httpd) for p in pods}
        registry = PodRegistry([p.url for p in pods], poll_interval_s=0.2)
        router = FleetRouter(
            registry, request_timeout_s=30.0,
            admission=AdmissionController(fair_share=4, max_backlog=16),
            retry_budget=RetryBudget(ratio=0.2, reserve=10.0),
        )
        router.start()
        httpd = route_serve(router, listen=f"127.0.0.1:{free_port()}")
        base = f"http://127.0.0.1:{httpd.server_address[1]}"

        import numpy as np
        rng = np.random.RandomState(9)  # seeded drill
        prompts = {name: [int(t) for t in rng.randint(1, 60, size=6)]
                   for name in ("hot", "cold1", "cold2")}
        # /v1/forward traffic, like the fleet drills: routing + admission
        # semantics are identical for every proxied verb, the output is
        # deterministic (argmax), and the short service time packs enough
        # in-window completions for the fairness bounds to have
        # statistics (a 4-token generate takes seconds under lockdep —
        # single-connection cold clients would finish ~nothing)
        expected = {}
        for name, prompt in prompts.items():
            r = requests.post(base + "/v1/forward",
                              json={"tokens": [prompt]})
            assert r.status_code == 200
            expected[name] = r.json()["logits_argmax"]

        results = {n: {"ok": 0, "shed": 0} for n in prompts}
        failures: list = []
        stop_at = time.monotonic() + 6.0
        lock = threading.Lock()

        def client(name: str) -> None:
            sess = requests.Session()
            while time.monotonic() < stop_at:
                try:
                    r = sess.post(
                        base + "/v1/forward",
                        json={"tokens": [prompts[name]]},
                        headers={"X-ModelX-Client": name},
                        timeout=30)
                except requests.RequestException as e:
                    with lock:
                        failures.append((name, repr(e)))
                    continue
                in_window = time.monotonic() <= stop_at
                with lock:
                    if r.status_code == 200:
                        if r.json()["logits_argmax"] != expected[name]:
                            failures.append((name, "wrong tokens"))
                        elif in_window:
                            # fairness counts only in-window completions:
                            # the hot client's queued tail drains after
                            # stop_at and would otherwise re-credit the
                            # monopoly the scheduler prevented
                            results[name]["ok"] += 1
                    elif r.status_code in (429, 503, 504):
                        if "error" not in r.json():
                            failures.append((name, "untyped shed"))
                        if in_window:
                            results[name]["shed"] += 1
                    else:
                        failures.append((name, r.status_code, r.text[:120]))

        threads = [threading.Thread(target=client, args=("hot",), daemon=True)
                   for _ in range(10)]
        threads += [threading.Thread(target=client, args=(n,), daemon=True)
                    for n in ("cold1", "cold2")]
        try:
            for t in threads:
                t.start()
            time.sleep(2.0)
            # seeded mid-storm kill: one pod dies under load
            kills[pods[0].url].kill()
            for t in threads:
                t.join(timeout=60)
            assert not failures, failures[:5]
            snap = router.snapshot()

            # (2) zero dropped: every request resolved as a valid 200 or
            # a typed shed; at least the cold clients kept real goodput
            # through the kill
            assert results["cold1"]["ok"] > 0
            assert results["cold2"]["ok"] > 0

            # (1) fair-share occupancy bounds: the hot client offered
            # 10x the load but converges to ~its fair slot share — each
            # cold client's goodput lands within a factor of the hot
            # client's PER-CONNECTION share, and the sheds concentrate
            # on the hot client
            hot, c1, c2 = (results[n]["ok"] for n in ("hot", "cold1", "cold2"))
            fair = jain_index([hot, (c1 + c2) * 5])
            # hot has 10 threads vs 2 cold threads: equal CLIENT shares
            # mean hot ~= c1 + c2; allow generous slack for the kill
            # window but rule out monopoly (FIFO would give hot ~10x)
            assert hot < 6 * (c1 + c2), results
            adm = snap["admission"]
            shed_hot = adm["clients"].get("hdr:hot", {}).get("shed", 0)
            shed_cold = sum(
                adm["clients"].get(f"hdr:{n}", {}).get("shed", 0)
                for n in ("cold1", "cold2"))
            if shed_hot + shed_cold > 0:
                assert shed_hot >= shed_cold, adm["clients"]
            assert fair is not None

            # (3) retry budget holds: total upstream attempts stay within
            # requests x (1 + ratio) + reserve — no retry amplification
            # even with a pod dying mid-storm
            m = snap["router"]
            logical = m["requests_total"]
            attempts = m["upstream_attempts_total"]
            assert attempts <= logical * 1.2 + 10 + 1, (attempts, logical)
            # the kill was absorbed: the dead pod is quarantined and the
            # survivor carried the storm
            assert not registry.pod(pods[0].url).healthy
        finally:
            httpd.shutdown()
            router.close()
            for p in pods:
                p.httpd.shutdown()
