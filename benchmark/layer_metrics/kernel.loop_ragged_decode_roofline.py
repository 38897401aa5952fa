"""`kernel.ragged_decode_roofline` for a configuration that states its
attention width as `num_attention_heads` x `head_dim`: that reader as it
stands (its count is `reduce/kernels.py ragged_decode_call`), handed the run
with the width under the key it reads."""
import copy

from benchmark import spec


def read(run):
    config = run.config
    if "num_attention_heads" not in config:
        return None
    as_read = copy.copy(run)
    as_read.config = dict(
        config, n_embd=config["num_attention_heads"] * config["head_dim"])
    return spec.load_reader(
        "per_layer", "kernel.ragged_decode_roofline").read(as_read)
