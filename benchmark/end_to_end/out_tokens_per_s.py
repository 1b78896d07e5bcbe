"""Output tokens that arrived inside the window, over its length."""


def read(ctx):
    return ctx["load"]["tokens_in_window"] / ctx["seconds"]
