"""Seconds from process start to the window's first request: backend
start, data on the device, index build and warm-up."""


def read(ctx):
    return ctx.setup_s
