"""Operations and bytes a configuration's training or a kernel's call
*requires*, as functions of shapes: two operations per multiply-add, no
recomputation, only what the mask allows.  One module per family of
configurations (``<family>.py``) and per kernel (``<kernel>.py``)."""
