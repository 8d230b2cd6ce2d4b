"""Wall time of all jobs in the window over all the loop trips they ran,
in ms: the window runs from the first job's call to the last job's
results on the host, and every loop of the program counts its trips."""


def read(record):
    if record["trace"] is not None or not record["iterations"]:
        return None
    return 1000.0 * record["window_s"] / record["iterations"]
