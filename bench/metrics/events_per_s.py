"""Events of the window whose flush group is durable (fsynced) by its end,
over the window's seconds on the host clock."""


def read(rec):
    w = rec.window
    return w.completed / w.seconds
