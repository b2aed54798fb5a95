"""Seconds of set-up spent in Session attach -> plan -> apply (and
``overlap_step`` on several chips), as ``build_mesh`` drives them: the
benchmark's own host span ``setup.plan``."""


def read(ctx):
    return ctx["spans"].get("setup.plan")
