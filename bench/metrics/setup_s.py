"""Set-up: imports, generating and loading the tables, starting the
server, compiling and warming up the cell's query."""


def read(run):
    return run.get("setup_s")
