"""Share of the traced window on chip 0 in which a collective op runs and
no other op does, in %: collectives the device waits for."""

import tpwork as TP


def read(ctx):
    flat = ctx["trace"]
    if not flat["devices"] or not flat["window"]:
        return None
    dev = sorted(flat["devices"])[0]
    lo, hi = flat["window"]
    split = TP.collective_split(flat["devices"][dev], (lo, hi))
    if split["ops"] == 0:
        return None
    ctx["log"](f"collective_exposed.tp4: {split['ops']} collective ops, "
               f"{split['collective'] / 1e6:.4f} ms under collectives, "
               f"{split['exposed'] / 1e6:.4f} ms of it exposed, window "
               f"{(hi - lo) / 1e6:.4f} ms")
    return 100.0 * split["exposed"] / (hi - lo)
