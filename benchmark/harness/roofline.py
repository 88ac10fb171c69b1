"""The roofline: the least time a chip with published `peaks` needs for
given operations and bytes, and which of the two bounds it."""


def seconds(flops, nbytes, peaks):
    t_flops = flops / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), ("compute" if t_flops > t_bytes
                                   else "memory")
