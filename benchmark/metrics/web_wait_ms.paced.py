"""Client latency less the server's total span, mean per reply (paced cells)."""

from harness import layer


def read(ctx):
    return layer.web_wait_ms(ctx)
