"""Device time of the FiLMed backbone over the support set per task (ms):
the traced steps' ops under the program's ``cnaps.class_stats`` scope,
forward and backward, the LITE H pass and no-grad complement and the
class sums and second moments included (``bench/harness/train_film.py``
``scope_seconds``)."""

SCOPES = ("cnaps.class_stats", "cnaps.class_stats/lite.complement")


def read(obs):
    t = obs.get("trace") or {}
    sc = t.get("scopes")
    if not sc or not any(k in sc for k in SCOPES):
        return None
    return 1e3 * sum(sc.get(k, 0.0) for k in SCOPES) / (
        t["steps"] * obs["tasks_per_step"])
