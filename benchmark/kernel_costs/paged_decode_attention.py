"""What `ops/pallas_kernels.paged_decode_attention` has to do, from shapes.

The yardstick of `paged_decode_attention_roofline`: the least the chip
could do for the calls, not what the implementation happens to move.
Re-fetched or copied data does not count."""


def attention(kv_tokens, n_heads, head_dim, n_layers, kv_itemsize):
    """One or more decode steps' attention over the paged cache, all
    layers: `kv_tokens` is the sum over steps and live slots of the tokens
    each attends (its cached length including the token just written).

    Bytes: every attended K and V row is read once. Flops: q.k and p.v,
    2 flops per multiply-add over each cached element. (flops, bytes)."""
    elems = kv_tokens * n_heads * head_dim * n_layers
    return 4.0 * elems, 2.0 * elems * kv_itemsize


def cost(facts):
    """(flops, bytes) of the decode steps made while the trace ran, from
    the serve_engine job's tallies; None when the run has none."""
    traced, kv = facts.get("traced"), facts.get("kv")
    if not traced or not kv:
        return None
    return attention(traced["kv_tokens"], kv["n_heads"], kv["head_dim"],
                     kv["n_layers"], kv["itemsize"])
