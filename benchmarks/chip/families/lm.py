"""Runs a chain-LM cell: a closed loop of clients over ``ServeEngine``.

Each client has one request in flight; the round after it completes, the
client submits its next one. Request lengths come from the traffic file's
tables, whose order the seed shuffles once per pass through them, so every
seed serves the same sizes. Token ids are drawn from the seed. The weights
are the deployment's, drawn from the configuration's ``weight_seed``: the
bucket programs hold them as constants, so weights that changed with the
run's seed would make every run compile its programs anew.

Set-up: the engine pre-builds every padded lm entry count up to the slot
count (an lm feed round's topology is its padded entry count alone), waits
for the builds, then starts all clients at once with first requests whose
lengths are staggered so that completions spread over the rounds, in
groups of the sizes the traffic file lists (each group size is a count of
slots that refill in one round, which the engine stages as one program).
It runs until every client has turned over ``setup_turnovers`` requests
and the compile queue is empty.
"""

from __future__ import annotations

import time

import numpy as np

from harness import load_module

PENDING = "PENDING"
COMPLETED = "COMPLETED"


class _Rec:
    __slots__ = ("req", "client", "t_sub", "seen", "t_last", "pad", "n0",
                 "fed0")

    def __init__(self, req, client, t_sub, pad):
        self.req = req
        self.client = client
        self.t_sub = t_sub
        self.seen = 0
        self.t_last = 0.0
        self.pad = pad
        self.n0 = 0        # tokens served before the window opened
        self.fed0 = 0      # real tokens fed before the window opened


def real_fed(rec: _Rec) -> int:
    """Real tokens (prompt tokens past the padding, then served tokens fed
    back) the engine has run through the cell for this request."""
    req = rec.req
    return max(0, req.n_fed - rec.pad) + max(0, len(req.out) - 1)


def make_workload(cfg: dict, seed: int):
    """The program's chain LM at the configuration's widths, its weights
    drawn from the configuration's ``weight_seed`` (``seed``, the run's,
    drives the traffic only)."""
    from repro.models.chains import ChainLM

    if cfg["embed"] != cfg["hidden"] or cfg["layers"] != 1:
        raise SystemExit("ChainLM is one LSTM layer with embed = hidden")
    return ChainLM(cfg["hidden"], cfg["weight_seed"], vocab=cfg["vocab"])


class Run:
    """One run of an lm cell."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, workloads,
                 engine, counter):
        self.cfg = cfg
        self.traffic = traffic
        self.seed = seed
        self.eng = engine
        self.counter = counter
        self.ref = load_module("refs", cfg["reference"])
        self.n_clients = int(traffic["clients"])
        if self.n_clients > cfg["max_slots"]:
            raise SystemExit("more clients than slots: the loop would queue")
        self.tables = (list(traffic["prompt_len"]), list(traffic["max_new"]))
        self.rng = np.random.default_rng([seed, 1])
        self._order: list[tuple[int, int]] = []
        self.live: list[_Rec | None] = [None] * self.n_clients
        self.done: list[_Rec] = []
        self.failed = 0
        self.w0 = None
        self.w1 = None
        self.tokens = 0
        self.ttft: list[float] = []
        self.itl: list[float] = []
        self.queue_wait: list[float] = []
        self.attempted = 0
        self.done_before = 0
        self.admit_pending: list[_Rec] = []

    # -- traffic ---------------------------------------------------------

    def _next_lengths(self) -> tuple[int, int]:
        if not self._order:
            p = self.rng.permutation(len(self.tables[0]))
            m = self.rng.permutation(len(self.tables[1]))
            self._order = [(self.tables[0][i], self.tables[1][j])
                           for i, j in zip(p, m)]
        return self._order.pop()

    def _submit(self, client: int, prompt_len: int, max_new: int) -> None:
        from repro.serve import lm_request

        prompt = [int(t) for t in
                  self.rng.integers(0, self.cfg["vocab"], prompt_len)]
        req = lm_request(prompt, max_new, arrival=self.eng._now)
        pad = len(self.ref.padded_prompt(prompt, self.cfg)) - prompt_len
        rec = _Rec(req, client, time.perf_counter(), pad)
        self.eng.submit(req)
        self.live[client] = rec
        self.admit_pending.append(rec)
        if self.w0 is not None and self.w1 is None:
            self.attempted += 1

    # -- set-up ----------------------------------------------------------

    def setup(self, phases: dict) -> None:
        eng = self.eng
        t = time.perf_counter()
        counts, c = [], 8
        while c < self.cfg["max_slots"]:
            counts.append(c)
            c *= 2
        counts.append(self.cfg["max_slots"])
        eng.prewarm({"version": 1, "families": {"lm": {"counts": counts}}})
        eng._compiler.drain()
        phases["bucket_builds_s"] = time.perf_counter() - t

        t = time.perf_counter()
        st = self.traffic["stagger"]
        groups = list(st["group_sizes"])
        groups += [1] * (self.n_clients - sum(groups))
        gap = st["span_rounds"] / len(groups)
        client = 0
        for gi, size in enumerate(groups):
            for _ in range(size):
                self._submit(client, st["prompt_len"], 1 + int(gi * gap))
                client += 1
        turned = [0] * self.n_clients
        goal = int(self.traffic["setup_turnovers"])
        quiet = 0
        while min(turned) < goal or quiet < 50:
            c0 = self.counter.total
            eng.step()
            for rec in self.after_step(time.perf_counter()):
                turned[rec.client] += 1
            busy = eng._compiler.pending_count() if eng._compiler else 0
            quiet = quiet + 1 if (self.counter.total == c0 and not busy) \
                else 0
        phases["warm_rounds_s"] = time.perf_counter() - t
        phases["warm_rounds"] = eng._round

    # -- the window ------------------------------------------------------

    def open_window(self, t0: float) -> None:
        self.w0 = t0
        self.done_before = len(self.done)
        self.attempted = sum(r is not None for r in self.live)
        for rec in self.live:
            rec.n0 = len(rec.req.out)
            rec.fed0 = real_fed(rec)

    def close_window(self, t1: float) -> None:
        self.w1 = t1

    def after_step(self, t1: float):
        """Stamp what this round delivered; resubmit for finished clients.
        Returns the finished records (set-up) — in the window the count of
        tokens delivered is what the round log keeps."""
        inwin = self.w0 is not None and self.w1 is None
        finished = []
        ntok = 0
        if self.admit_pending:
            still = []
            for rec in self.admit_pending:
                if rec.req.admit_round >= 0:
                    if inwin:
                        self.queue_wait.append(rec.req.t_admit - rec.t_sub)
                else:
                    still.append(rec)
            self.admit_pending = still
        for rec in self.live:
            req = rec.req
            n = len(req.out)
            if n != rec.seen:
                d = n - rec.seen
                if inwin:
                    ntok += d
                    if rec.seen == 0:
                        self.ttft.append(t1 - rec.t_sub)
                        d -= 1
                    elif rec.t_last >= self.w0:
                        self.itl.append(t1 - rec.t_last)
                        d -= 1
                    self.itl.extend([0.0] * max(d, 0))
                rec.seen = n
                rec.t_last = t1
            if req.status != PENDING:
                finished.append(rec)
        for rec in finished:
            if inwin and rec.req.status != COMPLETED:
                self.failed += 1
            self.done.append(rec)
            self.live[rec.client] = None
            self._submit(rec.client, *self._next_lengths())
        if inwin:
            self.tokens += ntok
            return ntok
        return finished

    def after_window(self) -> None:
        """One more round through ``step()`` once the window has closed,
        with the slot pool read before and after it, for the one-step
        check in :meth:`readings`. The round is the window's own program
        at the window's occupancy."""
        from repro.serve.scheduler import next_feed_token

        before = self._pool()
        fed = {}
        for rec in self.live:
            if rec is not None and rec.req.rid in before[1]:
                req = rec.req
                fed[req.rid] = (req, next_feed_token(req), req.n_fed,
                                len(req.out))
        self.eng.step()
        self.after_step(time.perf_counter())
        self.step_pair = (before, self._pool(), fed)

    def _pool(self) -> tuple[dict, dict]:
        """The slot pool's fields and each request's slot, read through
        ``serve/resilience.py:snapshot_engine``."""
        from repro.serve.checkpoint import decode_array
        from repro.serve.resilience import snapshot_engine

        snap = snapshot_engine(self.eng, reason="bench-check")
        pool = {f: decode_array(snap["pool"][f]) for f in ("h_out", "c_out")}
        return pool, {int(k): v[1]
                      for k, v in snap["scheduler"]["slot_of"].items()}

    # -- numbers ---------------------------------------------------------

    def end_to_end(self) -> dict:
        span = self.w1 - self.w0
        return {"tokens_per_s": self.tokens / span,
                "ttft_p95_ms": _p95_ms(self.ttft),
                "itl_p95_ms": _p95_ms(self.itl)}

    def timings(self) -> dict:
        from harness import timing

        return {"ttft": timing(self.ttft), "itl": timing(self.itl),
                "queue_wait": timing(self.queue_wait)}

    def model_flops(self) -> float:
        """Model operations the window did: every real token fed through
        the cell, and the head of every served token."""
        counts = load_module("counts", self.cfg["counts"])
        E, H, V = self.cfg["embed"], self.cfg["hidden"], self.cfg["vocab"]
        fed = served = 0
        for rec in list(self.live) + self.done[self.done_before:]:
            if rec is None:
                continue
            fed += real_fed(rec) - rec.fed0
            served += len(rec.req.out) - rec.n0
        return (fed * counts.cell_flops(E, H)
                + served * counts.head_flops(H, V))

    # -- correctness -----------------------------------------------------

    def readings(self, precision: str = "highest",
                 control: str | None = None, shift: int = 0) -> dict:
        """The compared numbers, against the reference at ``precision``.

        - ``logit_gap``: over every served token of the sampled finished
          requests and of the requests in flight at the close, the widest
          gap by which the served token's reference logit lies below the
          reference's best at that position. The reference runs over each
          prompt with its served tokens.
        - ``step_rms``: over the requests that the round after the window
          advanced (:meth:`after_window`), the root-mean-square difference
          between the slot pool's h (and c) rows after that round and one
          reference step from the pool's rows before it with the token the
          round fed, over the root-mean-square of the reference's rows; the
          larger of the two fields. One step, so rounding is not amplified
          by the recurrence over hundreds of tokens. ``step_max``, the
          largest single difference over the largest magnitude, is printed
          and not compared.

        With ``control`` set to a lower precision, the reference at that
        precision stands in for the program: its first token at each
        position takes the served token's place, and its step the pool's.
        A nonzero ``shift`` plants the fault of a token altered where it is
        produced: each served (or control) token moves by ``shift`` ids.
        """
        w = self.ref.weights(self.cfg, self.cfg["weight_seed"])
        k = self.traffic["check_finished"]
        done = [r for r in self.done[self.done_before:]
                if r.req.status == COMPLETED and r.req.out]
        pick = []
        if done:
            longest = max(range(len(done)),
                          key=lambda i: len(done[i].req.out))
            rng = np.random.default_rng([self.seed, 2])
            rest = [i for i in range(len(done)) if i != longest]
            extra = rng.choice(len(rest), size=min(k - 1, len(rest)),
                               replace=False) if rest and k > 1 else []
            pick = [done[longest]] + [done[rest[i]] for i in extra]
        inflight = [r for r in self.live if r is not None and r.req.out]
        seqs, served, starts = [], [], []
        for rec in pick + inflight:
            req = rec.req
            feed = self.ref.padded_prompt(req.prompt, self.cfg)
            seqs.append(feed + list(req.out[:-1]))
            served.append(list(req.out))
            starts.append(len(feed) - 1)
        V = self.cfg["vocab"]
        gap = 0.0
        n_tokens = 0
        if seqs:
            if control:
                _, cfirst, _, _, _ = self.ref.forward(w, seqs, control)
            picks = []
            for b, (toks, s) in enumerate(zip(served, starts)):
                chosen = (cfirst[b][s:s + len(toks)] if control
                          else np.asarray(toks, np.int64))
                picks.append([0] * s + [int(t) for t in (chosen + shift) % V])
            best, _, picked, _, _ = self.ref.forward(w, seqs, precision,
                                                     picks)
            for b, (toks, s) in enumerate(zip(served, starts)):
                sl = slice(s, s + len(toks))
                gap = max(gap, float(np.max(best[b][sl] - picked[b][sl])))
                n_tokens += len(toks)

        (pool0, slot0), (pool1, slot1), fed = self.step_pair
        rows = [(rid, tok, n0 + k0 == 0)
                for rid, (req, tok, n0, k0) in fed.items()
                if slot1.get(rid) == slot0[rid]
                and (req.n_fed, len(req.out)) != (n0, k0)]
        rms = worst = 0.0
        if rows:
            before = [pool0[f][[slot0[r] for r, _, _ in rows]]
                      for f in ("h_out", "c_out")]
            for x in before:      # a fresh request starts from zero state
                x[[i for i, r in enumerate(rows) if r[2]]] = 0.0
            toks = [t for _, t, _ in rows]
            ref = self.ref.step(w, toks, *before, precision)
            got = (self.ref.step(w, toks, *before, control) if control
                   else [pool1[f][[slot1[r] for r, _, _ in rows]]
                         for f in ("h_out", "c_out")])
            for r_rows, g_rows in zip(ref, got):
                diff = g_rows - r_rows
                rms = max(rms, float(np.sqrt(np.mean(diff ** 2)
                                             / np.mean(r_rows ** 2))))
                worst = max(worst, float(np.max(np.abs(diff)))
                            / float(np.max(np.abs(r_rows))))
        return {"logit_gap": gap, "step_rms": rms, "step_max": worst,
                "n_tokens_checked": n_tokens, "n_requests_checked": len(pick),
                "n_steps_checked": len(rows)}


def _p95_ms(xs) -> float:
    from harness import percentile

    return percentile(xs, 95) * 1e3
