"""Device time of the Simple CNAPs covariance per task (ms): the traced
steps' ops under the program's ``cnaps.covariance`` scope, forward and
backward: the class and task covariance blend, the ridge and the
Cholesky factorisations (``bench/harness/train_film.py``
``scope_seconds``)."""

SCOPES = ("cnaps.covariance",)


def read(obs):
    t = obs.get("trace") or {}
    sc = t.get("scopes")
    if not sc or not any(k in sc for k in SCOPES):
        return None
    return 1e3 * sum(sc.get(k, 0.0) for k in SCOPES) / (
        t["steps"] * obs["tasks_per_step"])
