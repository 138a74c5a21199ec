"""Share of the blocked layouts' edge slots that hold no arc (padding up
to each layout's edge budget), over every layout the cell reads."""


def read(run):
    slots = sum(f["slots"] for f in run.layouts.values())
    arcs = sum(f["arcs"] for f in run.layouts.values())
    return None if not slots else 1.0 - arcs / slots
