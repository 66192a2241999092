"""One client: send a request, wait for its answer, send the next.

The window closes at the first completion after ``seconds``, so that no
request is cut in half.
"""
from bench.traffic import Done, call


def run(requests, issue, name, seconds, clock, span):
    done = []
    t0 = clock()
    for req in requests:
        start = clock() - t0
        with span(name):
            answer, error = call(issue, req)
        end = clock() - t0
        done.append(Done(req, start, end, answer, error))
        if end >= seconds:
            break
    return t0, done
