"""Query rows of every request completed inside the window, over the
window's length."""


def read(ctx):
    w = ctx.window
    rows = sum(o.rows for o in w.outcomes
               if o.error is None and o.done and o.t_done <= w.t1)
    return rows / (w.t1 - w.t0)
