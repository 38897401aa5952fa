"""What the serving tests share about the shape of a step program
(`LLMEngine.step_groups`): where a planned row's tokens lie in a step's
arrays, and an engine steered back to the one rectangle a step used to be."""


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
    restricted to those requests (`LLMEngine.step(only=...)`)."""
    plans, streams, rids, n = spy_plans(engine), {}, {}, 0
    todo = sorted(enumerate(arrivals), key=lambda ia: ia[1][0])
    while todo or engine.has_unfinished():
        while todo and todo[0][1][0] <= n:
            i, (_, prompt, tokens, *own) = todo.pop(0)
            rids[i] = engine.add_request(
                prompt, max_new_tokens=tokens, **{**knobs, **dict(*own)})
            streams[rids[i]] = []
        probe = None if only is None or n not in only \
            else {rids[i] for i in only[n] if i in rids}
        for out in engine.step(only=probe):
            streams[out.request_id].append(out.token)
        n += 1
        assert n < 500, "the serve does not end"
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
