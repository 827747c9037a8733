"""HTTP + admission layer: requests the scheduler shed inside the window
(/metrics `sched.shed.total`, window delta)."""


def read(ctx):
    try:
        return (ctx["m_close"]["sched"]["shed"]["total"]
                - ctx["m_open"]["sched"]["shed"]["total"])
    except (KeyError, TypeError):
        return None
