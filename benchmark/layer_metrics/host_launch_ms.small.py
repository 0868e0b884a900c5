"""API / tape layer, small-register cells: host time of one application inside
the program, from its ``circuit.run`` span (cache lookup, mesh context, the
jitted call, the register's put; it ends before any sync): growth of the
span's total over growth of its count across the window."""


def read(m):
    before = m["before"]["spans"].get("circuit.run",
                                      {"count": 0, "total_s": 0.0})
    after = m["after"]["spans"].get("circuit.run")
    if after is None or after["count"] == before["count"]:
        return None
    return ((after["total_s"] - before["total_s"]) * 1e3
            / (after["count"] - before["count"]))
