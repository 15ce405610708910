"""edges_per_s: the GraphChallenge rate, undirected edges times whole
counts completed in the window, over the window's host seconds."""


def read(run):
    if run.loop != "closed" or not run.records:
        return None
    return run.work["edges"] * len(run.records) / run.window_s
