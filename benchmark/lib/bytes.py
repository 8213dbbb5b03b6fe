"""The least bytes a decode step must move through HBM, from the
configuration's sizes alone: every multiplied weight once, in the
bfloat16 the policy computes in, and the K and V of the live positions
of the active slots, in the cache's bfloat16.  Whatever else a step
copies (a whole page pool, a float32 master) is the program's choice and
is not counted, so the share falls as the program wastes more."""
from benchmark.lib.weights import family

BF16 = 2


def decode_step_min_bytes(cfg, live_positions):
    weights = family(cfg).matmul_params(cfg) * BF16
    kv = 2 * cfg["num_hidden_layers"] * cfg["hidden_size"] * BF16 * \
        int(live_positions)
    return weights + kv
