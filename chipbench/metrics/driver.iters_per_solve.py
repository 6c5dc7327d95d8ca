"""Mean CG iterations per solve (``SolveResult.iters_taken``)."""
import metriclib


def read(run):
    solved = metriclib.items(run)
    if not solved:
        return None
    return metriclib.iterations(run) / len(solved)
