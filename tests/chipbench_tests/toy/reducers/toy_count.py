"""Tests only: a reducer added as a file."""


def reduce(ctx, key: str):
    return ctx.window.get(key)
