"""Event logger the benchmark names in ``hyperspace.eventLoggerClass``.

The program instantiates it by its dotted name and hands it every
telemetry event; the harness reads the index-usage events of each query
from ``EventSink.events``.
"""


class EventSink:
    events: list = []

    def log_event(self, event) -> None:
        EventSink.events.append(event)
