"""From a profiler trace to numbers: device busy and idle time, time by
operation name, idle gaps by what the host was doing, exposed collectives;
and the table of published peaks (``peaks.json``)."""
