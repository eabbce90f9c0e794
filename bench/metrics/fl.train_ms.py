"""Self ms per epoch of the program's ``fl.train`` spans: local training: the megastep's one dispatch for every task's trainers (vmapped steps, DP, fake weights), the gather of the submissions in trainer order, their copy to the host and the blob puts (`fl/cohort.py`)."""
from harness.spans import self_ms


def read(run):
    return self_ms(run, r"^fl\.train$")
