"""Mean number of slots in use over the window's decode steps (the
``slots`` attribute of the ``generation.step`` spans)."""


def read(obs):
    slots = [attrs["slots"] for name, _, _, attrs in obs["spans"]
             if name == "generation.step" and "slots" in attrs]
    return sum(slots) / len(slots) if slots else None
