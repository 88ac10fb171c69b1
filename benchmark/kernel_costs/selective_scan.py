"""What `ops/pallas_kernels.selective_scan` has to do, from shapes.

The yardstick of `selective_scan_roofline`: the least the chip could do
for the calls made while the trace ran."""


def scan(rows, decode_rows, layers, d_inner, d_state):
    """`rows` tokens through `layers` Mamba layers' recurrence, float32:
    `decode_rows` of them one at a time from a state that comes in and
    goes back out (a decode step's slots), the rest in prompts, whose
    state stays on the chip.

    Bytes: a row's dt and input are read and its output written once
    (3 x d_inner), B and C (2 x d_state); a decode row also moves its
    state (d_state x d_inner) in and out. Flops: per state element one
    exponential, its argument, the decay, the drive (2), the add and the
    output's multiply-add: 7. (flops, bytes)."""
    flops = 7.0 * layers * rows * d_state * d_inner
    nbytes = 4.0 * layers * (rows * (3 * d_inner + 2 * d_state)
                             + decode_rows * 2 * d_state * d_inner)
    return flops, nbytes


def cost(facts):
    """(flops, bytes) of the scans of the traced stretch, from the
    serve_hybrid job's tallies; None when the run has none."""
    traced, cache = facts.get("traced"), facts.get("cache")
    if not traced or not cache or "decode_tokens" not in traced:
        return None
    return scan(traced["decode_tokens"] + traced["prefill_tokens"],
                traced["decode_tokens"], cache["recurrent_layers"],
                cache["d_inner"], cache["d_state"])
