"""Driver loops of the traffic mixes, one module each, named by a
traffic file's ``driver``."""
