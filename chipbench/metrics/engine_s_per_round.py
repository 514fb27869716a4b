"""The round engine and chain: each round's wall time less the self time of
the train, publish, score and merge spans inside it."""

LAYERS = ("train", "publish", "score", "merge")


def read(run):
    if run.spans is None:
        return None
    spent = sum(run.spans.self_s.get(layer, 0.0) for layer in LAYERS)
    return (run.window.window_s - spent) / run.rounds
