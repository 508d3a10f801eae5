"""Dynamic NoC energy of the scored placement per profiled transmission,
as the plain reference's replay of the last job computes it."""


def read(ctx: dict):
    ref = ctx["reference"]
    if ref is None or not ref["transmissions"]:
        return None
    return ref["dynamic_energy_pj"] / ref["transmissions"]
