"""Strategies and collectives: per query, the device time of the
collective operations (all-gather, collective-permute, reduce-scatter,
all-to-all, all-reduce, with their ``-start`` and ``-done`` forms), as
the mean over the chips. It reads the reduced trace's ``device_ops``,
which holds the ten longest operations of the window only: a collective
that is not among them is not counted, and the line says how many
were."""

COLLECTIVES = ("all-gather", "collective-permute", "reduce-scatter",
               "all-to-all", "all-reduce")


def read(run):
    r = run.reduced
    if not r or not r["n_device_ops"] or not r["queries"]:
        return None
    found = [(name, s) for name, s in r["device_ops"]
             if any(c in name for c in COLLECTIVES)]
    run.say(f"collectives among the {len(r['device_ops'])} longest device "
            f"operations (no others are seen): "
            + (" ".join(f"{name.split(' ')[0]}={s * 1e3:.3f}ms"
                        for name, s in found) or "none"))
    return sum(s for _, s in found) / len(r["queries"]) * 1e3
