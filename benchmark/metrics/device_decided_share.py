"""Device dispatch: the useful share of the lanes sent to the device —
lanes it decided UNSAT plus lanes whose device model verified on the
host, over all lanes (``dispatch_stats``).  Nothing to read where no
lane was sent."""


def read(run):
    lanes = sum(row["counters"]["lanes"] for row in run.rows)
    if not lanes:
        return None
    decided = sum(row["counters"]["unsat"] + row["counters"]["sat_verified"]
                  for row in run.rows)
    return decided / lanes
