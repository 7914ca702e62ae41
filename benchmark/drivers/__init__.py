"""One module per way of offering load, named by a traffic file's
``driver``: ``run(stack, traffic, requests, seconds, now_ns, mark)``."""
