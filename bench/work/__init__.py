"""Logical operations and bytes of each kernel and step, from shapes.

Logical means the work the served requests need: real prompt tokens, not
the padded bucket; live context, not the pages a kernel walks past. Keys
and values count at the configuration's serving type (bfloat16, 2 bytes),
so a kernel's share of its roofline does not depend on how the program
stores them.
"""
