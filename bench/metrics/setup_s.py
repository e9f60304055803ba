"""setup_s: seconds from process start to the window: building the engine
from the seed (weights, catalog, summary, live updates), compiling or
loading the programs, and warming the cell's bucket shapes."""


def read(ctx):
    return ctx.setup_s
