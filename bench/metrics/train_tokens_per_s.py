"""Fresh training tokens (rows x sequence length) of every step completed
in the window, over the window's seconds."""


def read(rec):
    w = rec["window"]
    return None if w["tokens"] is None else w["tokens"] / w["seconds"]
