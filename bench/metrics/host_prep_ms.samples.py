"""Host milliseconds per step spent preparing the step's input: taking
the batch's rows from the data set and putting them on the device.  The
harness's own span, around the driver's feed, over the whole window."""


def read(rec):
    w = rec["window"]
    return 1e3 * w["prep_s"] / w["steps"]
