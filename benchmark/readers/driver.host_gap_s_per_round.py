"""Device-idle seconds inside the traced rounds (whatever the host was
doing: plan, dispatch, metrics fetch, logging), over the rounds."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or "rounds" not in tr["idle_in_part_s"]:
        return None
    return tr["idle_in_part_s"]["rounds"] / ctx["rounds"]
