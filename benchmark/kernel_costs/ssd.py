"""What Mamba-2's recurrence has to do, from shapes: `ssd_state_update`
(ops/pallas_kernels), one decode step of every live slot of a layer.

The yardstick of `ssd_state_update_roofline`: the least the chip could do
for the calls made while the trace ran, not what the implementation happens
to move. (The prompt's chunked form, `ssd_chunk_scan`, is XLA einsums and no
kernel: it has no roofline.)"""


def state_update(rows, layers, heads, head_dim, d_state, n_groups):
    """`rows` decode rows (live slots x steps) through `layers` layers,
    float32. Bytes: a row's state (heads x head_dim x d_state) comes in and
    goes back out; its x and y (heads x head_dim each), dt (heads), B and C
    (n_groups x d_state each) once. Flops per state element: the decay's
    product, the drive's product (dt x is per channel, not per element), the
    add, and the output's multiply-add: 5. (flops, bytes)."""
    state = heads * head_dim * d_state
    flops = 5.0 * layers * rows * state
    nbytes = 4.0 * layers * rows * (
        2 * state + 2 * heads * head_dim + heads + 2 * n_groups * d_state)
    return flops, nbytes


def state_update_cost(facts):
    """(flops, bytes) of the state updates of the traced stretch, from the
    serve_parallel_hybrid job's tallies; None when the run has none."""
    traced, cache = facts.get("traced"), facts.get("cache")
    if not traced or not cache or "ssm_heads" not in cache \
            or "decode_tokens" not in traced:
        return None
    return state_update(traced["decode_tokens"], cache["recurrent_layers"],
                        cache["ssm_heads"], cache["ssm_head_dim"],
                        cache["d_state"], cache["n_groups"])
