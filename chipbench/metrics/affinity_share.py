"""Router (``serving/router.py`` ``ReplicaRouter``): the window's
sessions that ``prefix-affinity`` placed on a replica already holding
leading blocks of their prompt (the change in ``affinity_hits``), over
the sessions opened in the window.  Nothing to read without a router."""


def read(ctx):
    b, e = ctx["base"], ctx["end"]
    opened = e["opened"] - b["opened"]
    if "affinity_hits" not in e or opened <= 0:
        return None
    return 100.0 * (e["affinity_hits"] - b["affinity_hits"]) / opened
