"""The runners hold the system under test: the only modules of the
benchmark that import the program."""
