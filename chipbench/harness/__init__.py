"""The benchmark's own machinery: discovery, inputs, the calls into the
system under test, the plain references, spans and trace reduction."""
