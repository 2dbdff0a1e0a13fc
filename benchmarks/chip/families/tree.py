"""Runs a tree cell: a closed loop of clients over ``ServeEngine``, each
request one sentence's binary parse tree, classified at every node.

Each client has one sentence in flight; the round after its result
arrives, the client submits its next one. All clients start together, so
every round admits one synchronized wave of ``clients`` sentences. Sentence
lengths come from the traffic file's table, whose order the seed shuffles
once per pass through it: with as many clients as table entries, every wave
serves the same multiset of lengths. Bracketings follow
``repro.models.data.random_tree``'s rule (merge a random adjacent pair until
one node is left) and leaf tokens are Zipf ids over the vocabulary, both
drawn from the seed. The weights are the deployment's, drawn from the
configuration's ``weight_seed``.

Set-up: the policy is trained as for every cell; then the engine packs
``prewarm_waves`` waves drawn on a stream of their own (not the run's) and
builds their bucket specs, the compile service drains, and the clients
start. It runs until every client has turned over ``setup_turnovers``
requests and the compile queue is empty.

A wave's spec is fixed by its length multiset except for the bucket of its
deepest tree's height, the length of the internal-node run (a tree of n
leaves has n - 1 internal nodes, and the widest internal step lies far
inside its width bucket). About one wave in two hundred holds a tree
deeper than 16, too rare for the drawn waves to meet, so one more prewarm
wave has its longest sentence bracketed to height ``prewarm_depth``.
"""

from __future__ import annotations

import random
import time

import numpy as np

from harness import load_module

PENDING = "PENDING"
COMPLETED = "COMPLETED"


class _Rec:
    __slots__ = ("req", "client", "t_sub", "tree")

    def __init__(self, req, client, t_sub, tree):
        self.req = req
        self.client = client
        self.t_sub = t_sub
        self.tree = tree


def make_workload(cfg: dict, seed: int):
    """The program's binary Tree-LSTM at the configuration's widths, its
    weights drawn from the configuration's ``weight_seed`` (``seed``, the
    run's, drives the traffic only)."""
    from repro.models.trees import TreeWorkload

    if cfg["layers"] != 1:
        raise SystemExit("TreeWorkload serves one Tree-LSTM layer")
    return TreeWorkload(cfg["workload"], cfg["hidden"], cfg["weight_seed"],
                        embed=cfg["embed"], vocab=cfg["vocab"],
                        n_classes=cfg["classes"])


def nested(tree) -> object:
    """A ``TreeNode`` as plain nested tuples, the reference's input: a leaf
    is its token id, an internal node ``(left, right)``. Tuples of ints
    leave the collector's tracking, so a run's thousands of kept sentences
    add nothing to a full collection."""
    if tree.is_leaf:
        return int(tree.token)
    return (nested(tree.left), nested(tree.right))


def n_nodes(tree) -> tuple[int, int]:
    """``(leaves, internal nodes)`` of a nested-tuple tree."""
    if not isinstance(tree, tuple):
        return 1, 0
    a, b = n_nodes(tree[0]), n_nodes(tree[1])
    return a[0] + b[0], a[1] + b[1] + 1


class Traffic:
    """The cell's sentences: lengths from the table, shuffled once per
    pass, and a fresh bracketing and token ids for each sentence."""

    def __init__(self, table: list[int], vocab: int, seed: int, stream: int):
        self.table = list(table)
        self.vocab = vocab
        self.order_rng = np.random.default_rng([seed, stream])
        self.tree_rng = random.Random(
            int(np.random.default_rng([seed, stream, 1]).integers(1 << 62)))
        self._order: list[int] = []

    def next_tree(self):
        from repro.models.data import random_tree

        if not self._order:
            self._order = [self.table[i] for i in
                           self.order_rng.permutation(len(self.table))]
        return random_tree(self.tree_rng, self._order.pop(), self.vocab)

    def wave_graph(self, n: int, depth: int = 0):
        """The merged graph of a wave of ``n`` sentences, as the engine
        merges one round's requests. With ``depth``, the wave's longest
        sentence is bracketed to that height (:func:`deep_tree`)."""
        from repro.models.trees import tree_graph
        from repro.serve import graph_request
        from repro.serve.scheduler import merge_request_graphs

        trees = [self.next_tree() for _ in range(n)]
        if depth:
            i = max(range(n), key=lambda j: len(leaf_tokens(trees[j])))
            trees[i] = deep_tree(leaf_tokens(trees[i]), depth)
        reqs = [graph_request("tree", tree_graph([t])) for t in trees]
        return merge_request_graphs(reqs)[0]


def leaf_tokens(tree) -> list[int]:
    if tree.is_leaf:
        return [tree.token]
    return leaf_tokens(tree.left) + leaf_tokens(tree.right)


def height(tree) -> int:
    if tree.is_leaf:
        return 0
    return 1 + max(height(tree.left), height(tree.right))


def deep_tree(tokens: list[int], depth: int):
    """A tree over ``tokens`` of height ``depth`` (or ``len - 1`` if that
    is less): a left comb over the first ``depth`` leaves joined to a
    balanced tree over the rest, which must be no taller than the comb."""
    from repro.models.data import TreeNode

    def balanced(toks):
        if len(toks) == 1:
            return TreeNode(token=toks[0])
        mid = len(toks) // 2
        return TreeNode(left=balanced(toks[:mid]), right=balanced(toks[mid:]))

    k = min(depth, len(tokens))
    node = TreeNode(token=tokens[0])
    for t in tokens[1:k]:
        node = TreeNode(left=node, right=TreeNode(token=t))
    if k < len(tokens):
        node = TreeNode(left=node, right=balanced(tokens[k:]))
    return node


class Run:
    """One run of a tree cell."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, workloads,
                 engine, counter):
        self.cfg = cfg
        self.traffic = traffic
        self.seed = seed
        self.eng = engine
        self.counter = counter
        self.ref = load_module("refs", cfg["reference"])
        self.n_clients = int(traffic["clients"])
        self.sentences = Traffic(traffic["lengths"], cfg["vocab"], seed, 1)
        self.live: list[_Rec | None] = [None] * self.n_clients
        self.done: list[_Rec] = []
        self.failed = 0
        self.w0 = None
        self.w1 = None
        self.ttft: list[float] = []
        self.queue_wait: list[float] = []
        self.attempted = 0
        self.done_before = 0
        self.admit_pending: list[_Rec] = []
        self.lanes = None          # (real, padded) lanes of the window
        self._lanes0 = None

    def _submit(self, client: int) -> None:
        from repro.models.trees import tree_graph
        from repro.serve import graph_request

        tree = self.sentences.next_tree()
        req = graph_request("tree", tree_graph([tree]), arrival=self.eng._now)
        rec = _Rec(req, client, time.perf_counter(), nested(tree))
        self.eng.submit(req)
        self.live[client] = rec
        self.admit_pending.append(rec)
        if self.w0 is not None and self.w1 is None:
            self.attempted += 1

    # -- set-up ----------------------------------------------------------

    def setup(self, phases: dict) -> None:
        eng = self.eng
        t = time.perf_counter()
        warm = Traffic(self.traffic["lengths"], self.cfg["vocab"], self.seed,
                       2)
        graphs = [warm.wave_graph(self.n_clients)
                  for _ in range(int(self.traffic["prewarm_waves"]))]
        graphs.append(warm.wave_graph(self.n_clients,
                                      int(self.traffic["prewarm_depth"])))
        phases["prewarm_jobs"] = eng.prewarm(
            {"version": 1, "families": {"tree": {"graphs": graphs}}})
        eng._compiler.drain()
        phases["bucket_builds_s"] = time.perf_counter() - t

        t = time.perf_counter()
        for client in range(self.n_clients):
            self._submit(client)
        turned = [0] * self.n_clients
        goal = int(self.traffic["setup_turnovers"])
        quiet = 0
        while min(turned) < goal or quiet < 3:
            c0 = self.counter.total
            eng.step()
            for rec in self.after_step(time.perf_counter()):
                turned[rec.client] += 1
            busy = eng._compiler.pending_count()
            quiet = quiet + 1 if (self.counter.total == c0 and not busy) \
                else 0
        phases["warm_rounds_s"] = time.perf_counter() - t
        phases["warm_rounds"] = eng._round
        phases["bucket_programs_built"] = eng._compiler.stats["landed"]

    # -- the window ------------------------------------------------------

    def _lane_counts(self):
        st = self.eng.stats
        real = getattr(st, "n_single_lanes_real", None)
        padded = getattr(st, "n_single_lanes_padded", None)
        return None if real is None or padded is None else (real, padded)

    def open_window(self, t0: float) -> None:
        self.w0 = t0
        self.done_before = len(self.done)
        self.attempted = sum(r is not None for r in self.live)
        self._lanes0 = self._lane_counts()

    def close_window(self, t1: float) -> None:
        self.w1 = t1
        now = self._lane_counts()
        if now is not None and self._lanes0 is not None:
            self.lanes = (now[0] - self._lanes0[0], now[1] - self._lanes0[1])

    def after_step(self, t1: float):
        """Stamp what this round finished and resubmit for those clients.
        Returns the finished records in set-up, and their count in the
        window."""
        inwin = self.w0 is not None and self.w1 is None
        if self.admit_pending:
            still = []
            for rec in self.admit_pending:
                if rec.req.admit_round >= 0:
                    if inwin:
                        self.queue_wait.append(rec.req.t_admit - rec.t_sub)
                else:
                    still.append(rec)
            self.admit_pending = still
        finished = [rec for rec in self.live
                    if rec is not None and rec.req.status != PENDING]
        for rec in finished:
            if inwin:
                if rec.req.status == COMPLETED:
                    self.ttft.append(t1 - rec.t_sub)
                else:
                    self.failed += 1
            self.done.append(rec)
            self.live[rec.client] = None
            self._submit(rec.client)
        return len(finished) if inwin else finished

    def after_window(self) -> None:
        pass

    # -- numbers ---------------------------------------------------------

    def _window_done(self) -> list[_Rec]:
        return [r for r in self.done[self.done_before:]
                if r.req.status == COMPLETED and r.req.done_round >= 0
                and self.w0 <= r.req.t_done <= self.w1]

    def end_to_end(self) -> dict:
        from harness import percentile

        return {"ttft_p95_ms": percentile(self.ttft, 95) * 1e3}

    def timings(self) -> dict:
        from harness import timing

        return {"ttft": timing(self.ttft), "queue_wait": timing(self.queue_wait)}

    def model_flops(self) -> float:
        """Model operations of the requests finished in the window: every
        real node's cell and head, padding left out."""
        counts = load_module("counts", self.cfg["counts"])
        E, H, C = self.cfg["embed"], self.cfg["hidden"], self.cfg["classes"]
        leaves = internal = 0
        for rec in self._window_done():
            a, b = n_nodes(rec.tree)
            leaves += a
            internal += b
        return counts.tree_flops(leaves, internal, E, H, C)

    # -- correctness -----------------------------------------------------

    def readings(self, precision: str = "highest",
                 control: str | None = None, shift: int = 0) -> dict:
        """The compared numbers, against the reference at ``precision``.

        - ``logit_gap``: over ``check_finished`` requests finished in the
          window (drawn from the seed), the largest absolute difference
          between a served logit and the reference's, over every node's
          classes.
        - ``n_checked``: the requests compared, and ``n_nodes_checked`` the
          nodes.

        With ``control`` set to a lower precision, the reference at that
        precision stands in for the program. A nonzero ``shift`` plants the
        fault of an altered token: the reference reads every leaf's token
        moved by ``shift`` ids."""
        w = self.ref.weights(self.cfg, self.cfg["weight_seed"])
        done = self._window_done()
        k = min(int(self.traffic["check_finished"]), len(done))
        rng = np.random.default_rng([self.seed, 2])
        pick = [done[i] for i in sorted(rng.choice(len(done), size=k,
                                                   replace=False))] if k else []
        V = self.cfg["vocab"]
        gap = 0.0
        nodes = 0
        for rec in pick:
            tree = rec.tree
            got = (self.ref.forward(w, tree, control) if control
                   else np.asarray(rec.req.result, np.float32))
            want = self.ref.forward(
                w, _shifted(tree, shift, V) if shift else tree, precision)
            if got.shape != want.shape:
                return {"logit_gap": float("inf"), "n_checked": len(pick),
                        "n_nodes_checked": nodes}
            gap = max(gap, float(np.max(np.abs(got - want))))
            nodes += want.shape[0]
        return {"logit_gap": gap, "n_checked": len(pick),
                "n_nodes_checked": nodes}


def _shifted(tree, shift: int, vocab: int):
    if isinstance(tree, int):
        return (tree + shift) % vocab
    return (_shifted(tree[0], shift, vocab), _shifted(tree[1], shift, vocab))
