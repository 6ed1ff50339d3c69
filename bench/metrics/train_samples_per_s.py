"""Fresh training rows of every step completed in the window, over the
window's seconds."""


def read(rec):
    w = rec["window"]
    return w["rows"] / w["seconds"]
