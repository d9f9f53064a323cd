"""Edges traversed by the completed requests, counted from the operand's
shape (BFS: the input edge tuples of the component a search reaches;
PageRank: iters x the stored adjacency entries), over the whole window."""
from bench import readers


def read(run):
    return readers.rate(run, "edges")
