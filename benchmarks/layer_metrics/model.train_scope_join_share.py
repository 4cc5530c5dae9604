"""Layer ``model``: of the device time of the training step's runs in the
traced window, the percentage that fell on instructions the table the step
filed of itself knows. Near 100; a table of another program than the one that
ran shows here, and the ``model.train_*`` times are then not to be believed
(``harness/model_scopes.py``)."""

from benchmarks.harness import model_scopes


def read(ev):
    return model_scopes.train_join_share(ev)
