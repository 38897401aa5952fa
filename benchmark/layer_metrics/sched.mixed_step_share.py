"""Steps that carried a prompt chunk (`mixed_steps`) over all steps."""


def read(run):
    c = run.counters
    steps = sum(c.get(k, 0) for k in ("decode_steps", "mixed_steps",
                                      "verify_steps"))
    return (100.0 * c.get("mixed_steps", 0) / steps, int(steps)) \
        if steps else None
