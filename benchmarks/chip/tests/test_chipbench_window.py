"""The window's counting, on a fake engine that serves lm requests one
token per round: every token delivered inside the window counts, tokens of
requests still running count, a stalled round shows in the gaps, and a
request cut by the window's edge counts only what fell inside."""

import chipbench_paths  # noqa: F401  (first: the path to the benchmark)

import time

import pytest

import harness
from harness import load_module, percentile

lm = load_module("families", "lm")

CFG = {"vocab": 16, "max_slots": 4, "embed": 4, "hidden": 4,
       "reference": "chainlm", "counts": "lstm", "pad_token": 0,
       "prompt_bucket_min": 4}
TRAFFIC = {"clients": 2, "prompt_len": [4], "max_new": [6],
           "check_finished": 1}


class FakeEngine:
    """Admits what was submitted, feeds one padded prompt token per round,
    then serves one token per round; ``stall`` rounds sleep and serve
    nothing."""

    def __init__(self, round_s=0.002, stall=(), stall_s=0.05):
        self._now = 0.0
        self._round = 0
        self.stats = type("S", (), {"tier_rounds": {}})()
        self.queue: list = []
        self.live: list = []
        self.round_s = round_s
        self.stall = set(stall)
        self.stall_s = stall_s

    def submit(self, req):
        self.queue.append(req)

    def step(self):
        t = time.perf_counter()
        for req in self.queue:
            req.admit_round, req.t_admit = self._round, t
            req.feed = [0] * (4 - len(req.prompt)) + list(req.prompt)
            self.live.append(req)
        self.queue = []
        if self._round in self.stall:
            time.sleep(self.stall_s)
        else:
            time.sleep(self.round_s)
            for req in list(self.live):
                if req.n_fed < len(req.feed):
                    req.n_fed += 1
                    if req.n_fed < len(req.feed):
                        continue
                req.out.append(1)
                if len(req.out) >= req.max_new:
                    req.status = "COMPLETED"
                    self.live.remove(req)
        tiers = self.stats.tier_rounds
        tiers["bucketed"] = tiers.get("bucketed", 0) + 1
        self._round += 1
        self._now += 1.0


def drive(eng, seconds):
    run = lm.Run(CFG, TRAFFIC, 3, {"lm": None}, eng, harness.CompileCounter())
    run._submit(0, 4, 6)
    run._submit(1, 4, 500)      # still running when the window closes
    for _ in range(3):          # the window opens mid-prefill
        eng.step()
        run.after_step(time.perf_counter())
    w = harness.Window(run, seconds, run.counter, harness.GCCounter())
    w.drive()
    w.gcc.close()
    return run, w


def test_every_delivered_token_counts():
    run, w = drive(FakeEngine(), 0.2)
    assert run.tokens == sum(r[2] for r in w.rounds)
    assert run.tokens > 0
    # Two clients, one token a round each once prefill is done: no round
    # delivers more than two.
    assert max(r[2] for r in w.rounds) == 2
    # Requests running at the close count too.
    running = sum(len(r.req.out) - r.n0 for r in run.live if r is not None)
    assert running > 0
    finished = sum(len(r.req.out) - r.n0
                   for r in run.done[run.done_before:])
    assert run.tokens == running + finished
    e2e = run.end_to_end()
    assert e2e["tokens_per_s"] == pytest.approx(run.tokens / w.length_s)


def test_stalled_round_shows_in_the_gaps():
    run, w = drive(FakeEngine(stall=(15,), stall_s=0.06), 0.3)
    stalled = [r for r in w.rounds if r[1] - r[0] >= 0.06]
    assert len(stalled) == 1 and stalled[0][2] == 0
    # The gap over the stall is one sample, as long as the stall.
    assert max(run.itl) >= 0.06
    assert percentile(run.itl, 50) < 0.06


def test_stalled_round_says_where_the_host_was():
    run, w = drive(FakeEngine(stall=(10,), stall_s=0.5), 0.8)
    slow = w.diagnostics()["slowest_rounds"][0]
    assert slow["ms"] >= 500
    # The sampler found the driving thread inside the fake engine's sleep.
    assert slow["where"] and "step" in slow["where"][0][0]
    assert not w.stalls._thread.is_alive()


def test_request_cut_by_the_window_edges():
    run, w = drive(FakeEngine(), 0.1)
    first_sub = min(r.t_sub for r in run.done[:2] + run.live if r)
    # The first requests were mid-prefill when the window opened: their
    # first tokens fall inside, and their TTFT runs from their submission,
    # before the window.
    assert len(run.ttft) >= 2
    assert max(run.ttft) >= w.t0 - first_sub
    # No gap spans the window's opening: a gap between two tokens is never
    # longer than the round that delivered the second.
    longest_round = max(r[1] - r[0] for r in w.rounds)
    assert max(run.itl) <= longest_round + 1e-9
    # Window edges fall on round boundaries.
    assert w.t0 == w.rounds[0][0] and w.t1 == w.rounds[-1][1]


def test_p95_reports_its_sample_count():
    xs = [i / 1000 for i in range(1, 101)]
    t = harness.timing(xs)
    assert t["n"] == 100
    assert t["p95_ms"] == pytest.approx(95.05)
    assert t["p50_ms"] == pytest.approx(50.5)
    assert harness.percentile([], 95) == 0.0
