"""Requests answered per second: the requests whose logits were ready
inside the window, over the window's wall seconds."""


def read(ctx):
    win = ctx["window"]
    return win.served_in_window / win.seconds if win.served_in_window \
        else None
