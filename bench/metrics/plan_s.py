"""Seconds ``repro.api.plan`` took (host clock, part of set-up)."""


def read(ctx):
    return ctx.plan_s
