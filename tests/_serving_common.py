"""What the serving tests share about the shape of a step program
(`LLMEngine.step_groups`): where a planned row's tokens lie in a step's
arrays, an engine steered back to the one rectangle a step used to be, and
an engine whose every step's logits are kept (`Served`)."""
import numpy as np

import jax

from paddle_tpu.serving import LLMEngine


def row_origins(engine, rows, W):
    """`[(b, s), ...]`, a planned row each: token `j` of the row is element
    `[b, s + j]` of an array in the layout of the width-`W` step (the
    model's `ids`, its logits): `[rows, width]` for a program of one group,
    `[1, slots]` with the groups side by side for one of more."""
    groups = engine.step_groups(W)
    at, _ = engine._place_rows(rows, groups)
    if len(groups) == 1:
        return [(i, 0) for _, i in at]
    first = [sum(n * w for n, w in groups[:g]) for g in range(len(groups))]
    return [(0, first[g] + i * groups[g][1]) for g, i in at]


class Served:
    """An engine over `model` whose every step's logits are kept:
    `logits[request][position]` is the `[vocab]` row that chose the token at
    `position + 1`; `plans` the steps' rows `(request, lane, start, count)`."""

    def __init__(self, model, **engine_kw):
        self.model = model
        self.logits, self.plans = {}, []
        self._plans, self._origins, self._seen = [], [], []
        plain = model.forward

        def forward(ids, caches=None):
            logits, caches = plain(ids, caches=caches)
            jax.debug.callback(lambda lg: self._seen.append(np.asarray(lg)),
                               logits._array)
            return logits, caches

        model.forward = forward
        self.engine = LLMEngine(model, **engine_kw)
        run_rows = self.engine._run_rows

        def planned(rows, W, step_id=0):
            self._plans.append([
                (r.req.request_id, r.req.lane, r.start, r.count)
                for r in rows])
            self._origins.append(row_origins(self.engine, rows, W))
            return run_rows(rows, W, step_id)

        self.engine._run_rows = planned

    def step(self, only=None):
        outs = self.engine.step(only=only)
        jax.effects_barrier()
        assert len(self._plans) == len(self._seen)
        for plan, origins, lg in zip(self._plans, self._origins, self._seen):
            for (rid, _, start, count), (b, s) in zip(plan, origins):
                for j in range(count):
                    self.logits.setdefault(rid, {})[start + j] = lg[b, s + j]
        self.plans += self._plans
        self._plans, self._origins, self._seen = [], [], []
        return outs

    def run(self):
        while self.engine.has_unfinished():
            self.step()

    def serve(self, prompts, max_new_tokens):
        """Serve `prompts` to the end; the request ids, and the answers
        under them in `outputs`."""
        rids = [self.engine.add_request(p, max_new_tokens=max_new_tokens)
                for p in prompts]
        reqs = [self.engine.get_request(r) for r in rids]
        self.run()
        self.outputs = {r: list(q.output_ids) for r, q in zip(rids, reqs)}
        return rids

    def worst(self, reference_logits, rid, prompt):
        """Largest gap between the served logits of `rid` at every position
        it was fed and `reference_logits(model, ids)`, the reference's full
        forward over prompt + answer."""
        ids = (prompt + list(self.outputs[rid]))[:-1]
        want = np.asarray(reference_logits(self.model, ids))
        got = self.logits[rid]
        assert sorted(got) == list(range(len(ids)))
        return max(np.abs(got[p] - want[p]).max() for p in got)


def prompts_of(lengths, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, vocab, n)] for n in lengths]


def rectangle(engine):
    """`engine`, its every step program the ONE rectangle `[max_batch,
    width]` that it was before a step had groups: the reference a test
    holds the grouped layout to. The rectangle is the one-group case of the
    same code, so this steers and replaces nothing; call it before the
    engine's first step."""
    assert not engine._step_fns and not engine._layouts
    engine.step_groups = lambda W: ((engine.max_batch, W),)
    return engine


def spy_plans(engine):
    """The list every later step of `engine` appends its plan to: `(width
    bucket, [(request, start, count, drafted), ...])`."""
    plans, run_rows = [], engine._run_rows

    def planned(rows, W, step_id=0):
        plans.append((W, [(r.req.request_id, r.start, r.count, len(r.draft))
                          for r in rows]))
        return run_rows(rows, W, step_id)

    engine._run_rows = planned
    return plans


def serve(engine, arrivals, only=None, **knobs):
    """Serve `arrivals`, `(step before which it is added, prompt, tokens to
    make[, knobs of its own])` each, to the end. Returns `(streams,
    plans)`: the tokens of each request in the order given, as the steps
    emitted them, and `spy_plans`' list with a request named by its place
    in `arrivals`. `only`: `{step: indices}` steps
    restricted to those requests (`LLMEngine.step(only=...)`). A step is
    named by its place among the steps LAUNCHED (`step_count`): a call may
    launch the next step before it reads the one in flight, and an idle
    engine takes the next arrival at once. A step a call launched ahead of
    the read cannot be a probe's: every key of `only` must be reached."""
    plans, streams, rids, calls = spy_plans(engine), {}, {}, 0
    todo = sorted(enumerate(arrivals), key=lambda ia: ia[1][0])
    probes = dict(only or {})
    while todo or engine.has_unfinished():
        n = engine.step_count          # the step the next launch makes
        while todo and (todo[0][1][0] <= n or not engine.has_unfinished()):
            i, (_, prompt, tokens, *own) = todo.pop(0)
            rids[i] = engine.add_request(
                prompt, max_new_tokens=tokens, **{**knobs, **dict(*own)})
            streams[rids[i]] = []
        assert not any(k < n for k in probes), ("launched ahead", probes, n)
        probe = None if n not in probes \
            else {rids[i] for i in probes.pop(n) if i in rids}
        for out in engine.step(only=probe):
            streams[out.request_id].append(out.token)
        calls += 1
        assert calls < 500, "the serve does not end"
    assert not probes, probes
    index = {rid: i for i, rid in rids.items()}
    return ([streams[rids[i]] for i in range(len(arrivals))],
            [(W, [(index[rid], *row) for rid, *row in rows])
             for W, rows in plans])


# A wave that puts, with chunks of 8, four lanes and two chunk rows a step,
# every kind of row beside every other: (arrival step, prompt length, tokens)
#   step 0: A's and B's first chunks (two chunk rows, no decode row)
#   step 1: A's second chunk beside B's decode row
#   step 2: A's last chunk (3 tokens) and C's first beside B's decode row;
#           D's first chunk is the THIRD chunk row: it waits a step
#   step 3: C's second and D's first chunk beside A's and B's decode rows
#   step 4: C's and D's last chunks are ONE token each (17 = 2 x 8 + 1,
#           9 = 8 + 1): they ride the decode group with A and B
WAVE = ((0, 19, 9), (0, 5, 12), (2, 17, 6), (2, 9, 5))


def wave_of(prompts):
    """`serve`'s arrivals for `WAVE` over `prompts` (`WAVE`'s lengths)."""
    assert [len(p) for p in prompts] == [n for _, n, _ in WAVE]
    return [(at, p, tokens) for (at, _, tokens), p in zip(WAVE, prompts)]


def check_wave_plans(plans):
    """`WAVE` was planned as its comment says: the rows of steps 0-4 as
    `(request, count)`."""
    a, b, c, d = range(4)
    got = [[(rid, count) for rid, _, count, _ in rows]
           for _, rows in plans[:5]]
    assert got == [
        [(a, 8), (b, 5)],
        [(a, 8), (b, 1)],
        [(a, 3), (b, 1), (c, 8)],               # d waits: no third chunk row
        [(a, 1), (b, 1), (c, 8), (d, 8)],
        [(a, 1), (b, 1), (c, 1), (d, 1)],
    ], got
    assert [W for W, _ in plans[:5]] == [8, 8, 8, 8, 1]
