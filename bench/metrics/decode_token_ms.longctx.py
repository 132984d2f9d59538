"""Model step, decode: device time of the scanned decode executable
(``jit_multi``) per token it delivered in the traced window, ms."""


def read(ctx):
    from bench.trace import decode_token_ms
    return decode_token_ms(ctx)
