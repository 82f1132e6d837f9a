"""All tokens of all optimizer steps completed in the window over the whole
window (host clock; the window ends when the last step's loss and
parameters are ready): the whole job's rate, not per chip."""


def read(run):
    r = run["records"]
    if r.get("kind") != "train":
        return None
    return r["tokens"] / r["window_s"]
