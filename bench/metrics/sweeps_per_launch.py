"""Mean number of sweeps the window's launches ran. A launch runs its
batch's sweep loop until its slowest rider stops (converged, or
``max_iterations``), so this is the most sweeps among its riders."""
from lib.stats import sweeps_per_launch


def read(run, trace):
    launches = run.data.get("launches", ())
    return sweeps_per_launch(launches) if launches else None
