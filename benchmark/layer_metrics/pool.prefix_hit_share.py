"""Prompt tokens served from cached blocks (`prefix_cache_hit_tokens`) over
the prompt tokens of the requests whose prompt has been read (they have a
first token), whole load: the ramp is where prompts are read today."""


def read(run):
    read_in = sum(len(s.prompt) for s in run.samples if s.token_times)
    if not read_in:
        return None
    return 100.0 * run.load_counters.get("prefix_cache_hit_tokens", 0) \
        / read_in, read_in
