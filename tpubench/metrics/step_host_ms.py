"""Host time per dispatched batch inside ``RetrievalServer.step`` outside
its wait for the device (``serve.await``): packing the queries, the plan
call that enqueues the program, fan-out of the replies. The server's
``step_host_us`` counter over its batches. Layer: host dispatch
(``RetrievalServer.step``, ``SearchPlan.retrieve_batch``)."""


def read(run):
    c = run.counters
    if "step_host_us" not in c or not c.get("batches"):
        return None
    return c["step_host_us"] / c["batches"] / 1e3
