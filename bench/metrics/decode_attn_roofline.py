"""Kernel ``kernels/decode_attn.py``: share of its memory roofline, %.

Bytes: the live packed K/V the kernel must read in the traced decode steps
(``bench.costs.decode_attn_bytes``, exact from the per-slot lengths), over
the chip's peak HBM bandwidth; the kernel moves far fewer operations than
bytes, so memory bounds it.  Time: the device time of the kernel's ops."""

# On a TPU the trace names each op by its HLO text; the fused decode kernel
# is the custom call returning the flash triple (num, m, l) in f32.
KERNEL = (r"^%[\w.-]+ = \(f32\[[0-9,]+\]\{[^}]*\}, f32\[[0-9,]+,1\]\{[^}]*\}, "
          r"f32\[[0-9,]+,1\]\{[^}]*\}\) custom-call\(")


def read(ctx):
    from bench import costs
    from bench.trace import op_time
    t, info = ctx["trace"], ctx["traced"]
    if not t or not info.get("contexts"):
        return None
    secs, n = op_time(t, KERNEL)
    if not n or secs <= 0:
        return None
    nbytes = costs.decode_attn_bytes(info["contexts"], ctx["dims"], ctx["pol"])
    return 100.0 * nbytes / ctx["peaks"]["hbm_bytes_per_s"] / secs
