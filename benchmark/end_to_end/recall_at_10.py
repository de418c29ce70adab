"""Mean recall@10 of every query row answered in the window, against
the plain reference."""


def read(ctx):
    return ctx.recall
