"""What `ops/pallas_kernels.paged_diff_attention` has to do, from shapes.

The yardstick of `paged_diff_attention_roofline` (the shared cache) and
`paged_diff_attention_ring_roofline` (the window layers' rings): the least
the chip could do for the calls, not what the implementation happens to
move. A whole pool mapped into VMEM to touch part of it does not count."""


def attention(attended, n_kv_heads, head_dim, kv_itemsize):
    """Grouped differential attention over `attended` cached tokens:
    the sum over decode steps, live slots AND reading layers of the
    tokens each call attends (a window layer no more than its window;
    the shared cache once per layer that reads it: eight reads are eight
    reads, the least a chip can do without keeping it on the chip).

    Bytes: every attended row of every K/V head, K and V, is read once.
    Flops: a pair of K/V heads serves 4 query rows, each scored against
    one head's K (head_dim) and applied to both heads' V (2 x head_dim),
    2 flops per multiply-add. (flops, bytes)."""
    pairs = n_kv_heads // 2
    flops = 2.0 * attended * pairs * 4 * (head_dim + 2 * head_dim)
    return flops, 2.0 * attended * n_kv_heads * head_dim * kv_itemsize


def _cost(facts, kind):
    traced, cache = facts.get("traced"), facts.get("cache")
    if not traced or not cache or not traced.get("attended"):
        return None
    return attention(traced["attended"][kind], cache["n_kv_heads"],
                     cache["head_dim"], cache["itemsize"])


def shared_cost(facts):
    """(flops, bytes) of the traced decode steps' reads of the shared
    cache, from the serve_hybrid job's tallies; None when it has none."""
    return _cost(facts, "shared_kv")


def ring_cost(facts):
    """The same for the window layers' rings."""
    return _cost(facts, "window_kv")
