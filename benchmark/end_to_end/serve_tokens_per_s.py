"""Tokens that whole engine steps delivered inside the window, over the time
those steps span.  A request delivers each output token at the return of the
step that made it, and its prompt's tokens (the client's count, so a prefix
cache cannot shrink them) at the return of the step that made its first output
token: the prefill that read them.  The interval opens at the end of the step
that was running at 0 (at 0 where the engine was idle) and closes at the end
of the first step that ends at or after ``--seconds``; requests submitted in
the lead-in count by what is delivered inside it.  A preempted request counts
once, by its final run; a refused or failed one delivers nothing.  The
arithmetic is ``benchmark/lib/loadgen.py`` ``delivered``.

Its grain.  Where the closing step falls costs a step's tokens and no longer
a request's, so runs of one tree whose schedules stand alike in the window
read 0.01-0.4 % apart (five backlog cells, PR 49).  It is not nothing: a
prompt is credited whole at its first token, and a backlog's window holds a
section of a ramp (prefill first, decode filling behind it), so a schedule
that stands EARLIER or LATER against the window reads another section.  One
Kimi run with the 40 s window laid 1 s apart over it read 33,755, 33,600,
33,479, 33,346, 33,005, 32,904, 32,530 tokens/s from 3 s early to 3 s late
(+1.2 % to -2.4 %; 0.3-1.1 % a second of shift, one way), the GPT-2 backlog
9,540 to 9,462 (0.4 %, either way); the completed count moved 3 % a second
in the same run.  A program x % faster stands later in its ramp by x % of the
time run, so a gain in a ramped cell reads about a fifth short; a host freeze
in the lead-in shifts a run the other way (``PERF.md`` section 2)."""
from benchmark.lib import loadgen


def read(record, cell):
    return loadgen.delivered(record["raw"])["delivered_tokens_per_s"]
