"""Window seconds over solves completed, one caller waiting for each
answer before sending the next (closed loop), in milliseconds."""


def read(run):
    solves = run.loop.get("solves")
    if not solves:
        return None
    return 1000.0 * run.window_s / len(solves)
