"""Device time of the query path per task (ms): the traced steps' ops
under the program's ``learner.query`` scope, forward and backward: the
FiLMed backbone over the queries, the Mahalanobis head and the
cross-entropy (``bench/harness/train_film.py`` ``scope_seconds``)."""

SCOPES = ("learner.query",)


def read(obs):
    t = obs.get("trace") or {}
    sc = t.get("scopes")
    if not sc or not any(k in sc for k in SCOPES):
        return None
    return 1e3 * sum(sc.get(k, 0.0) for k in SCOPES) / (
        t["steps"] * obs["tasks_per_step"])
