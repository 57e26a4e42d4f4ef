"""Mean time from a request's due time to its dispatch into the engine
(``ServeRequest.dispatch_t``): intake, queueing and the top-up hold."""
import numpy as np


def read(run):
    waits = run.dispatch - run.due
    waits = waits[~np.isnan(waits)]
    return 1e3 * float(waits.mean()) if waits.size else None
