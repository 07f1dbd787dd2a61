"""Operation and byte counts of the problem each kernel and step solves,
from problem shapes alone: a later kernel that does the same problem is
judged on the same work."""
