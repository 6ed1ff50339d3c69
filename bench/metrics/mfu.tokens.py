"""Model FLOP utilisation of the whole step: the model FLOPs of every
step completed in the window (``flops.round_flops``: the forward and
backward passes of each round's 1 + n_local updates, no recomputation),
over the window's seconds, over the bf16 peak of the chips the step
uses, in %."""


def read(rec):
    w, peaks = rec["window"], rec["peaks"]
    if peaks is None:
        return None
    rate = rec["round_flops"] * w["steps"] / w["seconds"]
    return 100.0 * rate / (rec["mesh_devices"] * peaks["bf16_flops"])
