"""Continuous-batching scheduler policy (the Orca-style iteration loop).

``ServingScheduler`` decides, at each scheduler step, which queued requests to
prefill into free decode slots — FCFS, with at most ``max_prefills_per_step``
prefills interleaved per step so an arrival burst can't starve running
decodes (TPOT protection). The device-side mechanics (prefill, slot insert,
decode step) live in ``serving/engine.py``; this module is pure host policy,
so it is exactly simulable under the virtual clock.

``hol_bypass_limit`` relaxes strict FCFS under block-aware admission: when
the queue head's KV footprint cannot fit but a later request's can, up to
``limit`` later requests may be admitted past the stuck head before
admissions stop until the head clears — work keeps flowing without unbounded
starvation of the big request. 0 (the default) preserves strict FCFS.

``policy="weighted_fair"`` (serving.tenants) replaces global FCFS with
start-time fair queuing (SFQ) across tenants: every admission charges its
token cost against the tenant's virtual-finish tag at ``cost / weight``, and
the queued request with the LOWEST start tag wins the next slot — so over
any busy interval each tenant's admitted tokens converge to its weight
share, while a tenant alone in the queue still gets every slot
(work-conserving). Per-tenant token buckets (``token_budget_per_s`` /
``token_budget_burst``) gate admission exactly under the virtual clock;
an over-budget tenant is DEFERRED, never shed. The FCFS head-of-line
bypass generalizes naturally: a winner blocked by the capacity predicate
keeps its low tag and is overtaken for one step by the next-best tenant's
candidate — bounded by construction, one candidate per tenant per step.

``simulate_static_batching`` is the baseline the continuous scheduler is
measured against in tier-1: classic whole-batch serving, where a batch of
``n_slots`` requests decodes until its LONGEST member finishes before any new
request starts. The shared virtual cost model (decode step / prefill token)
makes the comparison apples-to-apples.
"""


class ServingScheduler:
    """FCFS admission from the bounded queue into free slots."""

    def __init__(self, queue, n_slots, max_prefills_per_step=1,
                 policy="fcfs", hol_bypass_limit=0, tenants=None):
        if policy not in ("fcfs", "weighted_fair"):
            raise ValueError(f"unknown scheduling policy {policy!r}")
        self.queue = queue
        self.n_slots = n_slots
        self.policy = policy
        self.tenants = tenants
        self.max_prefills_per_step = max(int(max_prefills_per_step), 1)
        self.hol_bypass_limit = max(int(hol_bypass_limit), 0)
        # bounded-starvation window: how many requests have overtaken the
        # CURRENT stuck head (reset whenever the head is admitted/replaced)
        self._hol_head = None
        self._hol_bypasses = 0
        # weighted-fair state: global virtual time, per-tenant virtual
        # finish tags, per-tenant token buckets (tokens, last_refill_t)
        self._vnow = 0.0
        self._vfinish = {}
        self._buckets = {}

    def next_admissions(self, free_slots, now, can_admit=None):
        if self.policy == "weighted_fair":
            return self._fair_admissions(free_slots, now, can_admit)
        return self._fcfs_admissions(free_slots, now, can_admit)

    def _fcfs_admissions(self, free_slots, now, can_admit):
        """Requests to prefill this step: bounded by free slots AND the
        per-step prefill cap. ``now`` gates open-loop arrivals that were
        queued with a future arrival_time (virtual-clock simulations).

        ``can_admit``: optional capacity predicate (the paged KV pool's
        block-availability check). A head it rejects WAITS at the front —
        FCFS — unless ``hol_bypass_limit`` grants a later arrived-and-
        fitting request one of its bounded bypass slots."""
        out = []
        budget = min(free_slots, self.max_prefills_per_step)
        while budget > 0 and len(self.queue):
            head = self.queue.peek()
            if head.arrival_time is not None and head.arrival_time > now:
                break  # FCFS: nothing behind it may jump a not-yet-arrival
            if can_admit is not None and not can_admit(head):
                bypassed = self._try_bypass(now, can_admit)
                if bypassed is None:
                    break  # hold the line until running requests free blocks
                out.append(bypassed)
                budget -= 1
                continue
            if self._hol_head == head.request_id:
                # the stuck head finally fits: its starvation window closes
                self._hol_head = None
                self._hol_bypasses = 0
            out.append(self.queue.pop())
            budget -= 1
        for req in out:
            # admission stamp for the wide-event queue-wait breakdown; a
            # preemption-resume re-admission keeps the ORIGINAL stamp (its
            # queue-wait window closed at the first prefill)
            if req.admit_time is None:
                req.admit_time = now
        return out

    def _try_bypass(self, now, can_admit):
        """One bounded-starvation bypass of a blocked head, or None.

        The window is per stuck head: once ``hol_bypass_limit`` requests have
        overtaken it, nothing more is admitted until the head itself clears
        (so the big request is delayed by at most ``limit`` overtakers, not
        forever). The caller's ``can_admit`` carries the reservation
        counter, so a granted bypass reserves its blocks exactly like a
        head admission would."""
        if self.hol_bypass_limit <= 0:
            return None
        head = self.queue.peek()
        if self._hol_head != head.request_id:
            self._hol_head = head.request_id
            self._hol_bypasses = 0
        if self._hol_bypasses >= self.hol_bypass_limit:
            return None
        for i in range(1, len(self.queue)):
            cand = self.queue.peek_at(i)
            if cand.arrival_time is not None and cand.arrival_time > now:
                break  # arrivals are time-ordered; nothing further is due
            if can_admit(cand):
                self._hol_bypasses += 1
                return self.queue.pop_at(i)
        return None

    # -- weighted-fair admission (policy="weighted_fair") --------------------

    def _class_cfg(self, req):
        if self.tenants is None:
            return None
        return self.tenants.class_config(req.tenant_class)

    def _weight(self, req):
        cfg = self._class_cfg(req)
        return cfg.weight if cfg is not None else 1.0

    @staticmethod
    def _cost(req):
        """An admission's fair-share cost: the KV/compute footprint it may
        claim — prompt plus the full generation budget it reserved."""
        return float(req.prompt_len + req.max_new_tokens)

    def _bucket(self, req, now):
        """This tenant's token bucket, refilled to ``now``; None when the
        tenant has no budget configured. Refill is rate * elapsed virtual
        time, capped at burst — exact under the virtual clock."""
        cfg = self._class_cfg(req)
        if cfg is None or cfg.token_budget_per_s <= 0:
            return None
        burst = cfg.token_budget_burst or cfg.token_budget_per_s
        tokens, last = self._buckets.get(req.tenant_id, (burst, now))
        tokens = min(burst, tokens
                     + cfg.token_budget_per_s * max(now - last, 0.0))
        self._buckets[req.tenant_id] = (tokens, now)
        return tokens, burst

    def budget_ok(self, req, now):
        """Would the tenant's token bucket admit this request now? A request
        costing more than the burst is gated on a FULL bucket and runs the
        bucket into arrears — budgets defer admission, they never shed."""
        b = self._bucket(req, now)
        if b is None:
            return True
        tokens, burst = b
        return tokens + 1e-9 >= min(self._cost(req), burst)

    def charge(self, req, now):
        """Account one admission: deduct the token budget (arrears allowed)
        and advance the tenant's SFQ virtual-finish tag. Also the direct-
        admission hook for the engine's priority-preemption path. A resumed
        request (admit_time already stamped) was charged at its FIRST
        admission — a preemption must not double-bill the tenant."""
        if req.admit_time is not None:
            return
        cost = self._cost(req)
        b = self._bucket(req, now)
        if b is not None:
            tokens, _ = b
            self._buckets[req.tenant_id] = (tokens - cost, now)
        start = max(self._vfinish.get(req.tenant_id, 0.0), self._vnow)
        self._vnow = start
        self._vfinish[req.tenant_id] = start + cost / self._weight(req)
        req.admit_time = now

    def _fair_admissions(self, free_slots, now, can_admit):
        out = []
        budget = min(free_slots, self.max_prefills_per_step)
        while budget > 0 and len(self.queue):
            picked = self._fair_pick(now, can_admit)
            if picked is None:
                break
            out.append(picked)
            budget -= 1
        return out

    def _fair_pick(self, now, can_admit):
        """One SFQ selection, or None when nothing is eligible.

        Preemption returners outrank fresh arrivals in queue order (they
        hold their original seniority — ``push_front`` put them at the
        head). Among fresh arrivals, each tenant fields its OLDEST
        budget-eligible request, ordered by SFQ start tag (ties broken by
        arrival order). ``can_admit`` — the paged pool's reserving
        capacity predicate — is consulted only on would-be winners, in
        tag order: a blocked winner keeps its low tag and is overtaken
        for this step only, the fair-queue form of the bounded HOL
        bypass. Start tags are floored at the global virtual time, so a
        tenant idle through a busy interval re-enters at the frontier —
        weights share the BUSY intervals, they don't bank idle credit."""
        returners = []   # queue indices, in order
        fresh = {}       # tenant_id -> (start_tag, queue index)
        for i in range(len(self.queue)):
            cand = self.queue.peek_at(i)
            if cand.arrival_time is not None and cand.arrival_time > now:
                break  # arrivals are time-ordered; nothing further is due
            if cand.admit_time is not None:
                returners.append(i)
                continue
            if cand.tenant_id in fresh:
                continue  # within-tenant order stays strict FCFS
            if not self.budget_ok(cand, now):
                continue  # over budget: the tenant is deferred this step
            start = max(self._vfinish.get(cand.tenant_id, 0.0), self._vnow)
            fresh[cand.tenant_id] = (start, i)
        for i in returners + [i for _, i in sorted(fresh.values())]:
            cand = self.queue.peek_at(i)
            if can_admit is None or can_admit(cand):
                req = self.queue.pop_at(i)
                self.charge(req, now)
                return req
        return None


def simulate_static_batching(requests, n_slots, *, prefill_cost_per_token,
                             decode_step_cost, bucket_len):
    """Virtual cost of serving ``requests`` with static whole-batch batching.

    Requests are grouped FCFS into batches of ``n_slots``. Each batch pays
    one bucketed-prompt prefill (the batch pads to its longest prompt bucket,
    like a fixed-shape ``generate()`` call) plus ``max(max_new_tokens) - 1``
    decode steps — every short request idles its slot until the longest
    member finishes, which is exactly the utilization gap continuous batching
    closes. Returns ``(total_tokens, virtual_time)``.
    """
    total_tokens = 0
    t = 0.0
    reqs = list(requests)
    for i in range(0, len(reqs), n_slots):
        batch = reqs[i:i + n_slots]
        padded = max(bucket_len(r.prompt_len) for r in batch)
        # one batched prefill (generously: no extra cost for the extra rows),
        # whose logits yield every request's FIRST token — then decode steps
        # until the longest member is done
        t += padded * prefill_cost_per_token
        t += max(r.max_new_tokens - 1 for r in batch) * decode_step_cost
        total_tokens += sum(r.max_new_tokens for r in batch)
    return total_tokens, t
