"""What power retention's decode step has to do, from shapes:
`power_retention_step` (ops/pallas_kernels), one token of every live slot
through a layer's retention.

The yardstick of `power_retention_step_roofline`: the least the chip could
do for the calls made while the trace ran, not what the implementation
happens to move (its state holds 8320 lanes for 8256 features; the count
below is of the features)."""


def state_step(rows, layers, kv_heads, query_heads, head_dim):
    """`rows` decode rows (live slots x steps) through `layers` layers,
    float32. F = head_dim (head_dim + 1) / 2 features a K/V head. Bytes: a
    row's state (kv_heads x head_dim x F) and normaliser (kv_heads x F) come
    in and go back out; its q and o (query_heads x head_dim each), k and v
    (kv_heads x head_dim each) and gate (kv_heads) once. Flops per K/V head:
    the state's decay, outer product and add (3 a state element) and the
    normaliser's (2 an element), each query head's read-out of the state
    and the normaliser (a multiply-add an element of each), and phi of its
    k and queries (2 an element). (flops, bytes)."""
    F = head_dim * (head_dim + 1) // 2
    G = query_heads // kv_heads
    per_head = (3 * head_dim * F + 2 * F + 2 * G * (head_dim + 1) * F
                + 2 * (G + 1) * F)
    flops = float(layers * rows * kv_heads * per_head)
    nbytes = 4.0 * layers * rows * (
        kv_heads * (2 * head_dim * F + 2 * F + 2 * head_dim + 1)
        + 2 * query_heads * head_dim)
    return flops, nbytes


def state_step_cost(facts):
    """(flops, bytes) of the retention steps of the traced stretch, from the
    serve_retention job's tallies; None when the run has none."""
    traced, ret = facts.get("traced"), facts.get("retention")
    if not traced or not ret or "decode_tokens" not in traced:
        return None
    return state_step(traced["decode_tokens"], ret["layers"],
                      ret["kv_heads"], ret["query_heads"], ret["head_dim"])
